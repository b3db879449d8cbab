"""Sharding policy: logical-axis -> mesh-axis mapping for the whole framework.

The production mesh is (data=16, model=16) per pod, with an optional leading
"pod" axis for multi-pod runs (pure DP across pods).  Model code never names
mesh axes directly; it asks the active :class:`Policy` for PartitionSpecs so
the same code runs on 1 CPU device (policy disabled) and on a 512-chip mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Policy:
    """Maps logical tensor axes onto mesh axes.

    dp:     axes carrying the batch dimension, e.g. ("data",) or ("pod", "data").
    tp:     tensor-parallel axis name ("model") or None.
    fsdp:   axis that shards parameters FSDP-style ("data") or None.
    enabled: when False every helper degenerates to no-op (single device).
    """

    dp: Tuple[str, ...] = ()
    tp: Optional[str] = None
    fsdp: Optional[str] = None
    enabled: bool = False
    # decode-mode optimization (§Perf): slice activations on the fsdp axis
    # along the contraction dim so weights stay resident (no per-step FSDP
    # all-gather); XLA partial-sums and all-reduces the tiny activations.
    resident_decode: bool = False

    # ---- activation specs -------------------------------------------------
    def batch(self, *trailing: Optional[str]) -> P:
        """Spec for an activation whose dim0 is the (global) batch."""
        return P(self.dp if self.dp else None, *trailing)

    def constrain(self, x, spec: P):
        if not self.enabled:
            return x
        return jax.lax.with_sharding_constraint(x, spec)

    # Shorthand used throughout the model code: hidden states (B, S, D).
    def hidden(self, x, seq_axis: Optional[str] = None):
        return self.constrain(x, self.batch(seq_axis, None))

    # ---- divisibility-aware choices ----------------------------------------
    def axis_size(self, name: Optional[str]) -> int:
        if not self.enabled or name is None:
            return 1
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return 1
        return dict(mesh.shape).get(name, 1)

    def tp_size(self) -> int:
        return self.axis_size(self.tp)

    def shard_heads(self, n_heads: int, n_kv: int) -> bool:
        """True when attention can be head-sharded on the tp axis."""
        t = self.tp_size()
        return t > 1 and n_heads % t == 0 and n_kv % t == 0

    def dp_size(self) -> int:
        n = 1
        for a in self.dp:
            n *= self.axis_size(a)
        return n

    def cache_spec(self, batch: int, head_dim: int = 0) -> P:
        """Sharding for KV caches (B, S_max, KV, hd).

        resident_decode (§Perf): shard head_dim on tp — the per-position
        cache write is then local (no gather-update-scatter collectives)
        and attention partial-sums over hd with a tiny all-reduce.
        Baseline: shard the sequence dim on tp (flash-decode style).
        Batch goes on dp when it divides; long-context (batch=1) keeps
        sequence sharding for capacity."""
        if not self.enabled:
            return P()
        b_ok = batch % max(1, self.dp_size()) == 0
        if (self.resident_decode and b_ok and self.tp
                and head_dim % max(1, self.tp_size()) == 0):
            return P(self.dp, None, None, self.tp)
        if b_ok:
            return P(self.dp, self.tp, None, None)
        return P(None, tuple(self.dp) + ((self.tp,) if self.tp else ()),
                 None, None)

    def state_spec(self, batch: int, inner_div: bool = True) -> P:
        """Sharding for O(1) recurrent states (B, inner, ...)."""
        if not self.enabled:
            return P()
        b = self.dp if batch % max(1, self.dp_size()) == 0 else None
        return P(b, self.tp if inner_div else None)

    def maybe(self, name: Optional[str], size: int) -> Optional[str]:
        """Return the mesh axis only if `size` divides evenly over it."""
        if name is None or not self.enabled:
            return None
        return name if size % self.axis_size(name) == 0 else None


SINGLE = Policy()  # disabled policy for single-device smoke tests / unit tests


def make_policy(mesh: Mesh, multi_pod: bool = False,
                resident_decode: bool = False) -> Policy:
    dp = ("pod", "data") if multi_pod else ("data",)
    return Policy(dp=dp, tp="model", fsdp="data", enabled=True,
                  resident_decode=resident_decode)


# ---- parameter sharding rules ----------------------------------------------
# Parameters are pytrees of arrays; leaves carry a logical spec via the
# companion "specs" pytree produced by each model's `param_specs(cfg)`.
# Rules (trailing dims; leading stacked-layer dims are always unsharded):
#   ("fsdp", "tp")  - e.g. w_in (D, F): D on data, F on model
#   ("tp", "fsdp")  - e.g. w_out (F, D), embedding (V, D)
#   ("tp",)         - bias rows on the tp-sharded output dim
#   ()              - replicated (norm scales, small vectors)


def logical_to_spec(logical: Sequence[Optional[str]], policy: Policy,
                    shape: Sequence[int]) -> P:
    """Translate a logical spec tuple to a PartitionSpec, dropping any axis
    that does not divide evenly (defensive: keeps lowering robust)."""
    if not policy.enabled:
        return P()
    names = {"tp": policy.tp, "fsdp": policy.fsdp, "dp": policy.dp}
    out = []
    # right-align: logical spec describes the *trailing* dims
    pad = len(shape) - len(logical)
    out.extend([None] * pad)
    for dim, log in zip(shape[pad:], logical):
        if log is None:
            out.append(None)
            continue
        ax = names.get(log, log)
        if isinstance(ax, tuple):
            out.append(ax if dim % max(1, _tuple_size(policy, ax)) == 0 else None)
        else:
            out.append(policy.maybe(ax, dim))
    return P(*out)


def _tuple_size(policy: Policy, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= policy.axis_size(a)
    return n


def named_sharding_tree(specs_tree, shapes_tree, mesh: Mesh, policy: Policy):
    """Produce a pytree of NamedSharding matching a pytree of logical specs."""
    def one(spec, shaped):
        pspec = logical_to_spec(spec, policy, shaped.shape)
        return NamedSharding(mesh, pspec)
    return jax.tree.map(one, specs_tree, shapes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))
