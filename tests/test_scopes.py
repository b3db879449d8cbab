"""Named scopes of the device's work and the program's host spans.

  * every node of the compiled stream program has ops under
    ``stage/<node>``; the byte shifts and checksums land under
    ``bytes/shift`` and ``bytes/csum``, and no shift is a gather; the
    executor's observability blocks under ``obs/*`` exist exactly when
    observability is on;
  * the scopes cost nothing: the compiled HLO with its metadata stripped
    (and its instructions numbered in order) is the same with them and
    with ``jax.named_scope`` made a null context;
  * ``ingress/fill`` counts the frames and seconds of every fill, one
    span per call.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import echo
from repro.net import bytesops, frames as F, rpc
from repro.net.shard import ShardedFrameArena
from repro.net.stack import UdpStack, rpc_serve_topology
from repro.obs import host

IP_S = F.ip("10.0.0.1")
SHAPES = {"udp": (2, 16, 256), "rpc": (2, 8, 4224)}
_SCOPE = re.compile(r'op_name="[^"]*?\b((?:stage|obs|mgmt|bytes)/[\w.\-]+)')


def build(kind, **kw):
    if kind == "udp":
        return UdpStack([echo.make(port=7)], IP_S, mgmt_port=9909, **kw)
    return UdpStack([], IP_S, mgmt_port=9909, topo=rpc_serve_topology(
        [("rs", "rs_serve", rpc.MSG_RS_ENCODE)]), **kw)


def stream_hlo(stack, kind) -> str:
    n, b, w = SHAPES[kind]
    state = jax.eval_shape(stack.init_state)
    return stack.stream_fn().lower(
        state, jax.ShapeDtypeStruct((n, b, w), jnp.uint8),
        jax.ShapeDtypeStruct((n, b), jnp.int32)).compile().as_text()


def op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def scopes_in(text):
    return {m.group(1) for m in _SCOPE.finditer(text)}


@pytest.fixture(scope="module", params=["udp", "rpc"])
def compiled(request):
    stack = build(request.param)
    return request.param, stack, stream_hlo(stack, request.param)


def test_every_node_has_ops_under_its_stage_scope(compiled):
    kind, stack, text = compiled
    found = scopes_in(text)
    for node in stack.pipeline.order:
        assert f"stage/{node}" in found, (kind, node, sorted(found))
    assert "mgmt" in stack.pipeline.order


def test_shifts_and_checksums_land_under_bytes_scopes(compiled):
    kind, _, text = compiled
    names = op_names(text)
    assert any("/bytes/shift/" in n and "/stage/" in n for n in names)
    assert any("/bytes/csum/" in n and "/stage/" in n for n in names)
    # the byte scopes sit inside a stage, never inside an obs block
    assert not any("/obs/" in n and "/bytes/" in n for n in names)


@pytest.mark.parametrize("fn,scope", [
    (lambda p, n, m: bytesops.shift_left(p, n, m), "bytes/shift"),
    (lambda p, n, m: bytesops.shift_right(p, n, m), "bytes/shift"),
    (lambda p, n, m: bytesops.checksum16(p, 14, n), "bytes/csum"),
    (lambda p, n, m: bytesops.checksum16_with_pseudo(p, 14, n, n),
     "bytes/csum"),
])
def test_every_op_of_a_byte_helper_carries_its_scope(fn, scope):
    text = jax.jit(fn).lower(
        jnp.zeros((4, 64), jnp.uint8), jnp.full((4,), 20, jnp.int32),
        jnp.ones((4,), bool)).compile().as_text()
    ops = [n for n in op_names(text) if n.startswith("jit(")]
    assert ops and all(f"/{scope}/" in n for n in ops), ops


@pytest.mark.parametrize("fn", [bytesops.shift_left, bytesops.shift_right])
def test_a_per_row_shift_lowers_to_no_gather(fn):
    text = jax.jit(fn).lower(
        jnp.zeros((4, 64), jnp.uint8),
        jnp.full((4,), 20, jnp.int32)).compile().as_text()
    assert " gather(" not in text


def test_no_header_strip_is_a_gather(compiled):
    """The eth_rx and ip_rx strips (a per-row VLAN tag, IHL), like every
    other shift, are slices and selects in the stream program."""
    kind, _, text = compiled
    gathers = [n for ln in text.splitlines() if " gather(" in ln
               for n in op_names(ln)]
    assert not [n for n in gathers if "/bytes/shift/" in n], gathers


def test_obs_scopes_exist_exactly_when_observability_is_on(compiled):
    kind, _, on = compiled
    assert {"obs/counters", "obs/drops", "obs/recorder",
            "obs/series"} <= scopes_in(on)
    off = stream_hlo(build(kind, with_obs=False), kind)
    assert not {"obs/recorder", "obs/series"} & scopes_in(off)
    assert {"obs/counters", "obs/drops"} <= scopes_in(off)
    bare = stream_hlo(build(kind, with_telemetry=False), kind)
    assert not any(s.startswith("obs/") for s in scopes_in(bare))


def _canonical(text: str) -> str:
    """The HLO module without its metadata (the op_name and source
    frames, and the tables of source locations they point into), its
    instructions and computations numbered in order of appearance: XLA
    derives the names from the op names, which the scopes qualify."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    text = text[text.index("\n%"):]
    ids = {}
    text = re.sub(r"%[\w.\-]+",
                  lambda m: f"%{ids.setdefault(m.group(0), len(ids))}", text)
    params = {}
    return re.sub(r"\bparam_[\d.]+",
                  lambda m: f"p{params.setdefault(m.group(0), len(params))}",
                  text)


def test_scopes_cost_nothing(compiled, monkeypatch):
    """With ``jax.named_scope`` a null context the compiled program is
    the same, instruction for instruction: the scopes are metadata."""
    kind, _, scoped = compiled
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = stream_hlo(build(kind), kind)
    assert not any("stage/" in n for n in op_names(plain))
    assert _canonical(plain) == _canonical(scoped)


def test_ingress_fill_counts_every_fill_once():
    host.reset()
    frames = [bytes([i]) * (60 + i) for i in range(10)]
    arena = F.FrameArena(2, 8, 128)
    arena.fill(frames)
    arena.fill(frames[:3])
    sharded = ShardedFrameArena(2, 2, 8, 128)
    sharded.fill_rss({5001: frames[:4], 5002: frames[4:9]})
    assert np.array_equal(sharded.length[1, 0, :5],     # 5001 % 2 == 1
                          [len(f) for f in frames[:4]] + [0])
    sharded.fill_shards([frames[:2], frames[2:3]])
    c = host.counters()["ingress/fill"]
    assert c["calls"] == 4                      # one span per call
    assert c["frames"] == 10 + 3 + 9 + 3
    assert c["seconds"] > 0
    host.reset()
    assert host.counters() == {}


def test_span_adds_its_extras_and_time():
    host.reset()
    with host.span("test/span") as extra:
        extra["items"] = 3
    with host.span("test/span"):
        pass
    c = host.counters()["test/span"]
    assert c["calls"] == 2 and c["items"] == 3 and c["seconds"] >= 0
    host.reset()
