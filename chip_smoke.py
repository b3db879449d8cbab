#!/usr/bin/env python3
"""Bring-up check: the compiled stack's main path on a TPU chip.

    python chip_smoke.py [--seed N]             # phases a-c, one chip
    python chip_smoke.py --chips 4 [--seed N]   # phase d alone, four chips

Every phase drives the stack through the entry points a user calls
(``UdpStack`` -> ``stack.stream_fn()`` -> ``CompiledPipeline.run_stream``)
and checks what comes out against a host reference.  Frames, prompts and
weights all come from ``--seed``.

  a  UDP echo stream: the echo stack with the management plane, telemetry
     and observability on; 64 batches x 256 frames x 1536 B of 64-1518 B
     frames over ~1,000 flows, a few to an unbound port and a few with a
     corrupted IP checksum.  Every reply must equal the reply built on
     the host (``repro.net.frames``), and the device's drop table must
     name ``ip_csum`` at ``ip_rx`` and ``no_route`` at ``udp_rx``.
  b  ``rs_serve`` through the compiled Pallas RS(8,2) kernel: 256 4 KiB
     requests; parity must equal ``rs_encode_np`` and the stream program
     must hold ``tpu_custom_call``.
  c  ``lm_serve`` at the full width of qwen1.5-0.5b: 8 session slots of
     256 positions, four sessions prefilled by ``ServeEngine`` and adopted
     into the tile, 16 ``MSG_LM_GENERATE`` requests; tokens must equal a
     second engine's, and the weights must be arguments of the program.
  d  (``--chips 4`` only) ``ShardedStream`` of the RSS-replicated echo
     stack over four chips; each shard's egress must equal that shard's
     frames through an unsharded stack on one chip, and the sharded
     program must hold no collectives.

Each phase prints ``phase <x> {json}`` with its checks and two compile
times of its stream program: ``compile_s`` (the first compile: cold
unless the persistent cache already holds it) and ``compile_cached_s``
(the same program again after ``jax.clear_caches()``, read back from the
cache of ``repro.launch.compile_cache``).  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, outside a checkout, or when any phase fails, the script
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

IP_S = 0x0A000001                      # 10.0.0.1, the accelerator
SERVER_MAC = b"\x02\x00\x00\x00\x00\x01"
CLIENT_MAC = b"\x02\x00\x00\x00\x00\x02"
HDR = 14 + 20 + 8 + 9                  # eth + ipv4 + udp + rpc headers
ECHO_PORT, SERVE_PORT, MGMT_PORT = 7, 9400, 9909


def _compile(jitted, *args):
    """Lower and compile ``jitted`` for ``args`` twice: first as the cache
    finds it, then lowered afresh after dropping the in-memory caches, so
    the second compile is read back from the persistent cache.  Only the
    compile calls are timed, not the tracing."""
    import jax
    times = []
    for _ in range(2):
        jax.clear_caches()
        lowered = jitted.lower(*args)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        times.append(time.perf_counter() - t0)
    return compiled, {"compile_s": times[0], "compile_cached_s": times[1]}


def _reply(client_ip, client_port, server_port, msg, req_id, body):
    """The reply a client should receive, built on the host."""
    from repro.net import frames as F, rpc
    return F.udp_rpc_frame(IP_S, client_ip, server_port, client_port,
                           rpc.np_frame(msg, req_id, body),
                           dst_mac=CLIENT_MAC, src_mac=SERVER_MAC)


def _frame(outs, b, k):
    import numpy as np
    n = int(np.asarray(outs["tx_len"])[b, k])
    return np.asarray(outs["tx_payload"])[b, k, :n].tobytes()


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# phase a: UDP echo stream


def _echo_traffic(rng, n_frames, n_flows=1000):
    """(frames, per-frame (client_ip, port, req_id, body, kind)): sizes
    64-1518 B over ``n_flows`` flows; kind is "ok", "no_route" or
    "ip_csum" (8 frames each of the two bad kinds)."""
    from repro.net import frames as F, rpc
    sizes = rng.integers(64, 1519, n_frames)
    flows = rng.integers(0, n_flows, n_frames)
    bad = rng.choice(n_frames, 16, replace=False)
    kind = {int(i): "no_route" for i in bad[:8]}
    kind.update({int(i): "ip_csum" for i in bad[8:]})
    frames, meta = [], []
    for i in range(n_frames):
        f = int(flows[i])
        cip, port = F.ip(f"10.1.{f // 250}.{f % 250 + 1}"), 10000 + f
        body = rng.bytes(int(sizes[i]) - HDR)
        k = kind.get(i, "ok")
        fr = F.udp_rpc_frame(cip, IP_S,
                             port, 9 if k == "no_route" else ECHO_PORT,
                             rpc.np_frame(rpc.MSG_ECHO, i, body))
        if k == "ip_csum":
            fr = bytearray(fr)
            fr[18] ^= 0x5A                  # IP ident: header csum breaks
            fr = bytes(fr)
        frames.append(fr)
        meta.append((cip, port, i, body, k))
    return frames, meta


def phase_echo(seed, n_batches=64, batch=256, width=1536):
    import jax
    import numpy as np

    from repro.apps import echo
    from repro.launch.certify import assert_no_host_callbacks
    from repro.net import frames as F, rpc
    from repro.net.stack import UdpStack
    from repro.obs import export

    rng = np.random.default_rng(seed)
    stack = UdpStack([echo.make(port=ECHO_PORT)], IP_S, mgmt_port=MGMT_PORT)
    arena = F.FrameArena(n_batches, batch, width)
    frames, meta = _echo_traffic(rng, arena.capacity)
    arena.fill(frames)
    p, l = jax.device_put(arena.payload), jax.device_put(arena.length)

    state = stack.init_state()
    assert_no_host_callbacks(stack.run_stream, state, p, l)
    compiled, res = _compile(stack.stream_fn(), state, p, l)
    t0 = time.perf_counter()
    state, outs = compiled(state, p, l)
    jax.block_until_ready(outs)
    res["first_run_s"] = time.perf_counter() - t0

    alive = np.asarray(outs["alive"])
    served = np.asarray(outs["info"]["echo"])
    bad_replies = 0
    for i, (cip, port, req, body, k) in enumerate(meta):
        b, r = divmod(i, batch)
        if k == "ok":
            ok = (alive[b, r] and served[b, r] and _frame(outs, b, r)
                  == _reply(cip, port, ECHO_PORT, rpc.MSG_ECHO, req, body))
        elif k == "no_route":
            ok = not served[b, r]
        else:
            ok = not alive[b, r]
        bad_replies += not ok
    drops = export.drop_table(state, stack.pipeline)
    want = {"ip_rx": {"ip_csum": 8}, "udp_rx": {"no_route": 8}}
    res.update(frames=len(frames), replies_checked=len(frames) - 16,
               mismatches=bad_replies, drops=drops)
    _check(bad_replies == 0, f"{bad_replies} frames differ from the host")
    _check(drops == want, f"drop table {drops} != {want}")

    # a second window through the same program, state threaded
    t0 = time.perf_counter()
    state, outs = compiled(state, p, l)
    jax.block_until_ready(outs)
    res["second_run_s"] = time.perf_counter() - t0
    return res


# ---------------------------------------------------------------------------
# phase b: rs_serve through the compiled Pallas kernel


def phase_rs(seed, n_batches=4, batch=64, width=4224):
    import jax
    import numpy as np

    from repro.apps import reed_solomon as RS
    from repro.kernels.rs_encode import gf
    from repro.kernels.rs_encode.ref import rs_encode_np
    from repro.launch.certify import assert_no_host_callbacks
    from repro.net import frames as F, rpc
    from repro.net.stack import UdpStack, rpc_serve_topology

    rng = np.random.default_rng(seed + 1)
    topo = rpc_serve_topology([("rs", "rs_serve", rpc.MSG_RS_ENCODE)],
                              params={"rs": {"use_pallas": True}})
    stack = UdpStack([], IP_S, topo=topo)
    arena = F.FrameArena(n_batches, batch, width)
    cip = F.ip("10.2.0.2")
    blocks = [rng.bytes(RS.REQ) for _ in range(arena.capacity)]
    arena.fill([F.udp_rpc_frame(cip, IP_S, 20000 + i, SERVE_PORT,
                                rpc.np_frame(rpc.MSG_RS_ENCODE, i, blk))
                for i, blk in enumerate(blocks)])
    p, l = jax.device_put(arena.payload), jax.device_put(arena.length)

    state = stack.init_state()
    assert_no_host_callbacks(stack.run_stream, state, p, l)
    compiled, res = _compile(stack.stream_fn(), state, p, l)
    res["tpu_custom_call"] = "tpu_custom_call" in compiled.as_text()
    _check(res["tpu_custom_call"], "stream program holds no Pallas kernel")
    state, outs = compiled(state, p, l)

    gm = gf.generator_matrix(RS.K, RS.P)
    mismatches = 0
    for i, blk in enumerate(blocks):
        data = np.frombuffer(blk, np.uint8).reshape(RS.K, RS.REQ // RS.K)
        parity = rs_encode_np(data, gm).tobytes()
        b, r = divmod(i, batch)
        mismatches += _frame(outs, b, r) != _reply(
            cip, 20000 + i, SERVE_PORT, rpc.MSG_RS_ENCODE, i, parity)
    ops = int(np.asarray(state["apps"]["rs"]["ops"]))
    res.update(requests=len(blocks), mismatches=mismatches, served=ops)
    _check(mismatches == 0, f"{mismatches} parity replies differ")
    _check(ops == len(blocks), f"served {ops} of {len(blocks)}")
    return res


# ---------------------------------------------------------------------------
# phase c: lm_serve at full width


def phase_lm(seed, cfg=None, max_sessions=8, max_seq=256, sessions=4,
             prompt_len=32, steps=4):
    import jax
    import numpy as np

    from repro.apps import lm_server
    from repro.configs import get_config
    from repro.launch.certify import assert_no_host_callbacks
    from repro.models import model
    from repro.net import frames as F, rpc
    from repro.net.stack import UdpStack, rpc_serve_topology
    from repro.serve.engine import ServeEngine

    cfg = cfg or get_config("qwen1.5-0.5b")
    res = {"arch": cfg.name}
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        model.init_params(cfg, jax.random.key(seed)))
    res["init_params_s"] = time.perf_counter() - t0
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    res["param_bytes"] = param_bytes

    rng = np.random.default_rng(seed + 2)
    prompts = [rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
               for _ in range(sessions)]
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, params, max_sessions=max_sessions,
                      max_seq=max_seq)
    smap = {1000 + s: eng.new_session(pr) for s, pr in enumerate(prompts)}
    jax.block_until_ready(eng.cache)
    res["prefill_s"] = time.perf_counter() - t0

    # reference: a second engine; one step decodes every open session,
    # as one batch holding one request per session does in the tile
    ref = ServeEngine(cfg, params, max_sessions=max_sessions,
                      max_seq=max_seq)
    ref_sid = [ref.new_session(pr) for pr in prompts]
    want = []
    for _ in range(steps):
        toks = ref.step()
        want.append([int(toks[sid]) for sid in ref_sid])
    del ref

    tile = lm_server.make_tile(cfg, params, max_sessions=max_sessions,
                               max_seq=max_seq)
    stack = UdpStack([tile], IP_S, topo=rpc_serve_topology(
        [("lm", "lm_serve", rpc.MSG_LM_GENERATE)]))
    state = stack.init_state()
    state["apps"]["lm"] = lm_server.adopt_engine(state["apps"]["lm"], eng,
                                                 smap)
    del eng

    cip = F.ip("10.3.0.2")
    arena = F.FrameArena(steps, sessions, 128)
    arena.fill([F.udp_rpc_frame(cip, IP_S, 30000 + s, SERVE_PORT,
                                rpc.np_frame(rpc.MSG_LM_GENERATE,
                                             t * sessions + s,
                                             lm_server.encode_request(
                                                 1000 + s, 1, [])))
                for t in range(steps) for s in range(sessions)])
    p, l = jax.device_put(arena.payload), jax.device_put(arena.length)

    assert_no_host_callbacks(stack.run_stream, state, p, l)
    compiled, comp = _compile(stack.stream_fn(), state, p, l)
    res.update(comp)
    arg_bytes = compiled.memory_analysis().argument_size_in_bytes
    res["argument_bytes"] = arg_bytes
    _check(arg_bytes >= param_bytes,
           f"program arguments ({arg_bytes} B) do not hold the weights "
           f"({param_bytes} B)")
    state, outs = compiled(state, p, l)

    got = [[None] * sessions for _ in range(steps)]
    mismatches = 0
    for t in range(steps):
        for s in range(sessions):
            fr = _frame(outs, t, s)
            _, toks, ok = lm_server.decode_reply(fr[HDR:])
            got[t][s] = toks[0] if ok and len(toks) == 1 else None
            mismatches += fr != _reply(
                cip, 30000 + s, SERVE_PORT, rpc.MSG_LM_GENERATE,
                t * sessions + s,
                lm_server.encode_reply(1000 + s, [want[t][s]]))
    served = int(np.asarray(state["apps"]["lm"]["served"]))
    res.update(requests=steps * sessions, served=served,
               tokens=got, reference=want, mismatches=mismatches)
    _check(mismatches == 0, f"{mismatches} replies differ from the "
                            f"engine's tokens")
    _check(served == steps * sessions, f"served {served}")
    return res


# ---------------------------------------------------------------------------
# phase d: four chips


def phase_sharded(seed, shards=4, n_batches=16, batch=256, width=1536,
                  n_flows=1000):
    import jax
    import numpy as np

    from repro.apps import echo
    from repro.net import frames as F, rpc
    from repro.net.shard import ShardedStream
    from repro.net.stack import UdpStack, replicated_udp_topology

    def make_stack():
        apps = [echo.make(port=ECHO_PORT)]
        topo = replicated_udp_topology(apps, n_rx=2, policy="flow_hash")
        return UdpStack(apps, IP_S, topo=topo, mgmt_port=MGMT_PORT)

    _check(jax.device_count() >= shards,
           f"{shards} shards need {shards} devices, found "
           f"{jax.device_count()}")
    rng = np.random.default_rng(seed + 3)
    stack = make_stack()
    ss = ShardedStream(stack, shards=shards)
    arena = ss.make_arena(n_batches, batch, width)
    per_flow = arena.capacity // n_flows
    flows = {}
    for f in range(n_flows):
        cip, port = F.ip(f"10.4.{f // 250}.{f % 250 + 1}"), 10000 + f
        flows[port] = [F.udp_rpc_frame(
            cip, IP_S, port, ECHO_PORT,
            rpc.np_frame(rpc.MSG_ECHO, j,
                         rng.bytes(int(rng.integers(64, 1519)) - HDR)))
            for j in range(per_flow)]
    counts = arena.fill_rss(flows)
    p = jax.device_put(arena.payload, ss.sharding)
    l = jax.device_put(arena.length, ss.sharding)

    state = ss.init_state()
    compiled, res = _compile(ss.stream_fn(), state, p, l)
    hlo = compiled.as_text()
    found = [c for c in ("all-reduce", "all-gather", "collective-permute",
                         "all-to-all", "reduce-scatter") if c in hlo]
    res.update(shards=shards, frames_per_shard=counts, collectives=found)
    _check(not found, f"collectives in the sharded program: {found}")
    state, outs = compiled(state, p, l)
    outs = jax.tree.map(np.asarray, outs)

    ref_stack = make_stack()
    ref_fn = ref_stack.stream_fn()
    differ = []
    for s in range(shards):
        _, ref = ref_fn(ref_stack.init_state(),
                        jax.device_put(arena.payload[s]),
                        jax.device_put(arena.length[s]))
        if not all(np.array_equal(np.asarray(ref[k]), outs[k][s])
                   for k in ("tx_payload", "tx_len", "alive")):
            differ.append(s)
    res.update(served=int(outs["info"]["echo"].sum()),
               shards_differing=differ)
    _check(res["served"] == sum(counts), f"served {res['served']} of "
                                         f"{sum(counts)}")
    _check(not differ, f"shards {differ} differ from the unsharded stack")
    return res


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded phase")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {json.dumps(device)}",
              file=sys.stderr)
        return 1
    print(f"device {json.dumps(device)}", flush=True)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache {enable_compile_cache()}", flush=True)

    phases = ([("d", phase_sharded)] if args.chips == 4 else
              [("a", phase_echo), ("b", phase_rs), ("c", phase_lm)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            res = fn(args.seed)
        except Exception as e:         # report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            print(f"phase {name} FAILED: {type(e).__name__}: {e}",
                  flush=True)
            continue
        res["phase_s"] = time.perf_counter() - t0
        print(f"phase {name} {json.dumps(res)}", flush=True)
    if failed:
        print(f"chip_smoke: phases {failed} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
