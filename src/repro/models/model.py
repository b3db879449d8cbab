"""Model engine: scan-over-pattern-units execution of any ModelConfig.

Layout of params:
  {"embed": {...}, "units": {"p0": stacked, "p1": stacked, ...},
   "rem": [block params ...], "final_norm": {...}}
where "p<i>" corresponds to pattern position i, and every leaf under "units"
has a leading n_units axis consumed by lax.scan (fast compiles even for
64-layer models).  Remainder layers (n_layers % len(pattern)) are explicit.

Caches mirror the same structure.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.sharding import Policy, SINGLE


# ---------------------------------------------------------------------------
# init


@partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Random weights from ``key``, built as one compiled program."""
    cfg.validate()
    keys = jax.random.split(key, 4)
    units = {}
    for i, entry in enumerate(cfg.pattern):
        def one(u, _i=i, _e=entry):
            return B.block_init(cfg, _e, jax.random.fold_in(keys[0], u * 37 + _i))
        per_unit = [one(u) for u in range(cfg.n_units)]
        units[f"p{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_unit)
    rem = [B.block_init(cfg, e, jax.random.fold_in(keys[1], 1000 + j))
           for j, e in enumerate(cfg.remainder)]
    return {
        "embed": L.embed_init(cfg, keys[2]),
        "units": units,
        "rem": rem,
        "final_norm": L.rmsnorm_init(cfg),
    }


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    units = {f"p{i}": B.block_specs(cfg, e) for i, e in enumerate(cfg.pattern)}
    return {
        "embed": L.embed_specs(cfg),
        "units": units,
        "rem": [B.block_specs(cfg, e) for e in cfg.remainder],
        "final_norm": L.rmsnorm_specs(cfg),
    }


def param_shapes(cfg: ModelConfig):
    """Shape-only params via eval_shape (no allocation) for the dry-run."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


def count_params(cfg: ModelConfig) -> int:
    import math
    shapes = param_shapes(cfg)
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# caches


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               stacked: bool = True):
    """stacked=True: leaves carry a leading n_units axis (scan layout, used
    by prefill outputs).  stacked=False: per-unit list (decode layout — each
    donated leaf is updated in place with no full-stack copies)."""
    rem = [B.block_cache(cfg, e, batch, max_seq) for e in cfg.remainder]
    if stacked:
        units = {}
        for i, entry in enumerate(cfg.pattern):
            one = B.block_cache(cfg, entry, batch, max_seq)
            units[f"p{i}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_units,) + x.shape).copy(),
                one)
        return {"units": units, "rem": rem}
    units_list = [
        {f"p{i}": B.block_cache(cfg, entry, batch, max_seq)
         for i, entry in enumerate(cfg.pattern)}
        for _ in range(cfg.n_units)]
    return {"units_list": units_list, "rem": rem}


def cache_shapes(cfg: ModelConfig, batch: int, max_seq: int,
                 stacked: bool = True):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_seq, stacked))


def unstack_cache(cfg: ModelConfig, cache):
    """Convert a prefill (stacked) cache into the decode (list) layout."""
    if "units_list" in cache:
        return cache
    units_list = [
        jax.tree.map(lambda x: x[i], cache["units"])
        for i in range(cfg.n_units)]
    return {"units_list": units_list, "rem": cache["rem"]}


# ---------------------------------------------------------------------------
# embedding / frontends


def _embed_inputs(cfg: ModelConfig, params, batch: Dict[str, Any],
                  policy: Policy):
    if cfg.frontend == "audio_stub":
        # precomputed frame embeddings straight from the input spec
        h = batch["frames"].astype(cfg.cdtype)
        return policy.constrain(h, policy.batch(None, None))
    h = L.embed_apply(cfg, params["embed"], batch["tokens"], policy)
    if cfg.frontend == "vision_stub" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(h.dtype)
        P_ = img.shape[1]
        h = jnp.concatenate([img, h[:, P_:]], axis=1)
        h = policy.constrain(h, policy.batch(None, None))
    return h


# ---------------------------------------------------------------------------
# forward (train / prefill)


def _seq_res_spec(cfg: ModelConfig, h, policy: Policy, mode: str):
    """Sequence-sharded residual stream (Megatron-SP style): when attention
    is not head-sharded, the (B, S, D) carry between blocks is sharded on
    the tp axis along S — cutting live activation memory by tp_size.  The
    blocks' own constraints re-gather exactly where needed."""
    if mode == "decode" or not policy.enabled:
        return None
    if policy.shard_heads(max(cfg.n_heads, 1), max(cfg.n_kv_heads, 1)):
        return None
    S = h.shape[1]
    if policy.tp is None or S % max(1, policy.tp_size()) != 0:
        return None
    return policy.batch(policy.tp, None)


# parameters kept in fp32 even when compute is bf16 (numerics-sensitive)
_KEEP_F32 = {"lam", "A_log", "dt_bias", "D_skip", "router"}


def _cast_for_compute(cfg: ModelConfig, tree):
    """Cast matrix params to the compute dtype *before* the unit scan so the
    FSDP all-gathers inside the scan move bf16, not fp32 — halving both the
    gather traffic and the gathered-weight working set.  Gradients still
    accumulate in fp32 (astype is linear; its cotangent casts back)."""
    cd = cfg.cdtype
    if cd == jnp.float32:
        return tree

    def one(path, x):
        name = getattr(path[-1], "key", getattr(path[-1], "name", ""))
        if name in _KEEP_F32 or x.ndim < 2 or x.dtype != jnp.float32:
            return x
        return x.astype(cd)
    return jax.tree_util.tree_map_with_path(one, tree)


def _run_stack(cfg: ModelConfig, params, h, policy: Policy, *, mode,
               cache=None, pos=None):
    """Returns (h, new_cache or None)."""
    want_cache = mode in ("prefill", "decode")
    res_spec = _seq_res_spec(cfg, h, policy, mode)
    params = dict(params)
    params["units"] = _cast_for_compute(cfg, params["units"])
    params["rem"] = _cast_for_compute(cfg, params["rem"])

    def one_block(entry, bp, h, c):
        h, nc = B.block_apply(cfg, entry, bp, h, policy, mode=mode,
                              cache=c, pos=pos)
        if res_spec is not None:
            h = policy.constrain(h, res_spec)
        return h, nc

    if cfg.remat and mode == "train":
        # per-block remat: backward recomputes one block at a time, so the
        # live set is a single block's intermediates, not a whole unit's
        one_block = jax.checkpoint(
            one_block, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(0,))

    def unit_body(h, pu, cu):
        new_cs = {}
        for i, entry in enumerate(cfg.pattern):
            c = cu[f"p{i}"] if cu is not None else None
            h, nc = one_block(entry, pu[f"p{i}"], h, c)
            new_cs[f"p{i}"] = nc
        return h, (new_cs if want_cache else None)

    unit_list_out = None
    if cfg.n_units > 0:
        if cache is not None and "units_list" in cache:
            # decode layout: unrolled, per-unit donated leaves updated in
            # place (no stacked-cache copies)
            unit_list_out = []
            for i in range(cfg.n_units):
                pu = jax.tree.map(lambda x: x[i], params["units"])
                h, ncs = unit_body(h, pu, cache["units_list"][i])
                unit_list_out.append(ncs)
            unit_caches = None
        elif cache is not None:
            def scan_fn(h, xs):
                pu, cu = xs
                return unit_body(h, pu, cu)
            h, unit_caches = jax.lax.scan(scan_fn, h,
                                          (params["units"], cache["units"]))
        else:
            def scan_fn(h, pu):
                return unit_body(h, pu, None)
            h, unit_caches = jax.lax.scan(scan_fn, h, params["units"])
    else:
        unit_caches = None

    rem_caches = []
    for j, entry in enumerate(cfg.remainder):
        c = cache["rem"][j] if cache is not None else None
        h, nc = B.block_apply(cfg, entry, params["rem"][j], h, policy,
                              mode=mode, cache=c, pos=pos)
        rem_caches.append(nc)

    if not want_cache:
        return h, None
    if unit_list_out is not None:
        return h, {"units_list": unit_list_out, "rem": rem_caches}
    return h, {"units": unit_caches, "rem": rem_caches}


def forward(cfg: ModelConfig, params, batch: Dict[str, Any],
            policy: Policy = SINGLE, mode: str = "train"):
    """Full-sequence forward. Returns logits (B, S, v_pad)."""
    h = _embed_inputs(cfg, params, batch, policy)
    h = policy.constrain(h, policy.batch(None, None))
    h, _ = _run_stack(cfg, params, h, policy, mode="train")
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return L.lm_head(cfg, params["embed"], h, policy)


def loss_fn(cfg: ModelConfig, params, batch: Dict[str, Any],
            policy: Policy = SINGLE):
    logits = forward(cfg, params, batch, policy)
    return L.cross_entropy(cfg, logits, batch["labels"], policy)


# ---------------------------------------------------------------------------
# inference


def prefill(cfg: ModelConfig, params, batch: Dict[str, Any],
            policy: Policy = SINGLE):
    """Process the prompt; returns (last_token_logits (B, v_pad), cache)."""
    if not cfg.supports_decode:
        # encoder: "prefill" is a plain forward; no cache
        logits = forward(cfg, params, batch, policy, mode="train")
        return logits[:, -1], None
    h = _embed_inputs(cfg, params, batch, policy)
    h, cache = _run_stack(cfg, params, h, policy, mode="prefill")
    h = L.rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    logits = L.lm_head(cfg, params["embed"], h, policy)
    return logits[:, 0], cache


def decode_step(cfg: ModelConfig, params, cache, token, pos,
                policy: Policy = SINGLE):
    """One decode step. token: (B,) int32; pos: scalar int32 (cache slot &
    rope position of the incoming token). Returns (logits (B, v_pad), cache).
    """
    assert cfg.supports_decode
    h = L.embed_apply(cfg, params["embed"], token[:, None], policy)
    h, new_cache = _run_stack(cfg, params, h, policy, mode="decode", cache=cache,
                              pos=pos)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = L.lm_head(cfg, params["embed"], h, policy)
    return logits[:, 0], new_cache


def greedy_token(cfg: ModelConfig, logits):
    """Argmax over the un-padded vocab."""
    V = cfg.vocab
    iota = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    masked = jnp.where(iota < V, logits.astype(jnp.float32), -jnp.inf)
    return jnp.argmax(masked, axis=-1).astype(jnp.int32)
