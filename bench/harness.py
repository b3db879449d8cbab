"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the metrics.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``bench/configs/<config>.json``
and ``.py`` build the deployment, frame its requests and hold its
reference, ``bench/traffic/<mix>.json`` holds the mix (and may name a
generator of its own, ``bench/traffic/<name>.py``), and
``bench/metrics/<metric>.py`` reads each metric.

The served path is the program's own API.  Each window: the client's
frames (bytes made in set-up) go into an arena with ``FrameArena.fill``
(``fill_rss`` on several chips), the arena goes to the device with
``jax.device_put``, the compiled ``stream_fn()`` runs with the donated
state threaded through, and ``tx_payload`` / ``tx_len`` / ``alive`` and
the app's per-row served flag come back to the host.  At most two
windows are in flight, and a window leaves as soon as a slot is free:
in a closed loop the next pool window is always due; in an open loop a
window takes every request that is due, up to its capacity.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW_SPAN = "bench_window"
SPANS = ("fill", "put", "dispatch", "readback")
GIVE_UP_S = 60.0             # an answer not back this long after close
# A traced run records the device from the start of the window until at
# least this long and two windows have passed: a trace of every op is too
# large to hold for the whole window (an echo window is some 660,000 op
# events per chip).  Busy and idle time count from each device's first
# program run in that span, so the host's fill of the first window, while
# the pipeline is still empty, is left out.
TRACE_MIN_S = 1.0


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_file_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    mod = load_file_module(os.path.join(HERE, "configs", f"{name}.py"),
                           f"bench_config_{name}")
    return cfg, mod


def load_metric(name: str):
    return load_file_module(os.path.join(HERE, "metrics", f"{name}.py"),
                            "bench_metric_" + name.replace(".", "_"))


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


class Spans:
    """The benchmark's own host spans around its calls into the program:
    total seconds per name, and a ``TraceAnnotation`` in the profiler's
    trace while one is recorded."""

    def __init__(self, annotate: bool):
        self.total = collections.defaultdict(float)
        self.annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.total[name] += time.perf_counter() - t0


@dataclasses.dataclass
class Window:
    rows: np.ndarray            # (S, rows) request index per arena row
    outs: object = None
    arrivals: Optional[np.ndarray] = None    # open loop: arrival indices
    got: Optional[Dict[str, np.ndarray]] = None
    t_reply: float = 0.0
    measured: bool = True
    ok: Optional[np.ndarray] = None          # (S,) correct replies


class Compiles:
    """Counts XLA compilations while ``active``."""

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1


def make_traffic(mix, cfg, cfgmod, shards: int, rows: int, seed: int,
                 seconds: float) -> traffic.Pool:
    """The mix's requests for one run, framed as the configuration frames
    them (unless the mix's generator made the frames itself)."""
    pool = traffic.generator(mix)(
        mix, cfg, shards, rows, seed, seconds,
        lambda sizes: cfgmod.request_body_len(cfg, sizes))
    if pool.frames is None:
        pool.frames = cfgmod.requests(cfg, pool)
    return pool


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, bench: Optional[dict] = None,
             overrides: Optional[dict] = None,
             fault: Optional[Callable] = None, mix_overrides=None,
             keep: Optional[dict] = None, log=sys.stderr) -> dict:
    """Run ``cell`` once and return the result line's fields.

    ``overrides`` replaces keys of the configuration (tests use it for
    a small arena and one shard); ``fault`` wraps the compiled step
    (``fault(step) -> step``) to plant a fault under the timed path;
    ``keep``, where given, receives the run's context."""
    import jax

    bench = bench or load_benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}[cell]
    cfg, cfgmod = load_config(wl["config"])
    cfg.update(overrides or {})
    mix = traffic.load_mix(wl["traffic"])
    mix.update(mix_overrides or {})
    compiles = Compiles()

    t_build = time.perf_counter()
    system = cfgmod.build(cfg)
    S = system.shards
    n_batches, batch, _ = system.shape
    rows = n_batches * batch
    t_pool = time.perf_counter()
    pool = make_traffic(mix, cfg, cfgmod, S, rows, seed, seconds)
    cap = cfgmod.reply_cap(cfg, pool)
    t_warm = time.perf_counter()
    step = system.step if fault is None else fault(system.step)
    arenas = [system.new_arena(), system.new_arena()]
    spans = Spans(annotate=trace)
    box = {"state": system.init_state(), "k": 0}
    inflight: collections.deque = collections.deque()
    done: List[Window] = []

    if pool.loop == "closed":
        fills = [system.fill_input(pool.frames, pool.client_port, w)
                 for w in pool.windows]

    def dispatch(win: Window, fill_input):
        arena = arenas[box["k"] % 2]
        box["k"] += 1
        with spans("fill"):
            system.fill(arena, fill_input)
        with spans("put"):
            p, l = system.put(arena)
            jax.block_until_ready((p, l))
        with spans("dispatch"):
            box["state"], win.outs = step(box["state"], p, l)
            for v in jax.tree.leaves(win.outs):
                v.copy_to_host_async()
        inflight.append(win)

    def collect():
        win = inflight.popleft()
        with spans("readback"):
            got = system.readback(win.outs)
        win.t_reply = time.perf_counter()
        got["tx_payload"] = got["tx_payload"][..., :cap].copy()
        win.got, win.outs = got, None
        done.append(win)
        if tracer is not None and win.measured:
            tracer.after_collect()

    def open_window(first: int, count: int, measured=True) -> Window:
        arr = np.arange(first, first + count)
        req = arr % len(pool.frames)
        r = np.full((1, rows), -1, np.int64)
        r[0, :count] = req
        win = Window(rows=r, arrivals=arr if measured else None,
                     measured=measured)
        dispatch(win, system.fill_input(pool.frames, pool.client_port, r))
        return win

    # ---- set-up ends with one whole window through the same path
    tracer = None
    if pool.loop == "closed":
        dispatch(Window(rows=pool.windows[0], measured=False), fills[0])
    else:
        open_window(0, rows, measured=False)
    collect()
    # what set-up left behind is collected here, not in the window
    gc.collect()
    gc.freeze()
    tracer = _Tracer() if trace else None
    setup_s = time.perf_counter() - t_start
    print(f"setup_s {setup_s} (start to build {t_build - t_start}, build "
          f"{t_pool - t_build}, traffic {t_warm - t_pool}, compile and "
          f"first window {setup_s - (t_warm - t_start)})", file=log,
          flush=True)

    # ---- the measured window
    compiles.active = True
    for k in spans.total:
        spans.total[k] = 0.0
    if tracer is not None:
        tracer.open()
    t0 = time.perf_counter()
    if pool.loop == "closed":
        w = 0
        while time.perf_counter() - t0 < seconds or w == 0:
            if len(inflight) < 2:
                i = (w + 1) % len(pool.windows)
                dispatch(Window(rows=pool.windows[i]), fills[i])
                w += 1
            else:
                collect()
        while inflight:
            collect()
        due_abs = None
    else:
        due_abs = t0 + pool.due
        n, nxt = len(due_abs), 0
        while nxt < n or inflight:
            now = time.perf_counter()
            if inflight and (len(inflight) == 2
                             or inflight[0].outs["tx_len"].is_ready()):
                collect()
                continue
            if nxt < n and due_abs[nxt] <= now:
                m = int(np.searchsorted(due_abs, now, "right")) - nxt
                m = min(m, rows)
                open_window(nxt, m)
                nxt += m
                continue
            if now > t0 + seconds + GIVE_UP_S:
                break
            wait = (due_abs[nxt] - now) if nxt < n else 1e-3
            time.sleep(min(max(wait, 0.0), 2e-4 if inflight else 1e-3))
    t1 = done[-1].t_reply if done else time.perf_counter()
    compiles.active = False
    gc.unfreeze()
    if tracer is not None:
        tracer.stop()
    window_s = t1 - t0

    # ---- after the window: memory, then the reference and the check
    devs = jax.devices()[:S]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    states = system.shard_states(box["state"])
    box.clear()
    verdict = check(cfg, cfgmod, system, pool, done, states, cap, due_abs)
    verdict["checks"]["compiles_in_window"] = {
        "value": compiles.count, "limit": 0}

    measured = [w for w in done if w.measured]
    trace_summary = tracer.summary if tracer else None
    traced = measured if tracer else []
    ctx = dict(
        cell=cell, cfg=cfg, mix=mix, shards=S, seconds=seconds,
        setup_s=setup_s, window_s=window_s, spans=dict(spans.total),
        windows=len(measured),
        frames=int(sum((w.rows >= 0).sum() for w in measured)),
        trace=trace_summary, give_up_s=GIVE_UP_S,
        traced_rows=[(w.rows >= 0).sum(1) for w in traced],
        traced_ok=[w.ok for w in traced],
        device_kind=jax.devices()[0].device_kind, **verdict["window"])
    if keep is not None:
        keep.update(ctx, due=pool.due,
                    reply_times=np.array([w.t_reply for w in measured]),
                    t0=t0)
    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": S, "memory_peak_bytes": peak}
    out = {"correct": verdict["correct"],
           "attempted": verdict["window"]["attempted"],
           "failed": verdict["window"]["failed"],
           "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary.busy_s
        device["window_s"] = trace_summary.window_s
        out["breakdown"] = trace_summary.breakdown()
    out["checks"] = verdict["checks"]
    return out


class _Tracer:
    """The profiler over the start of the measured window, read back as
    a ``trace_reduce.Summary``.  It starts in set-up, so that the device
    is traced before the first window reaches it; the ``bench_window``
    span opens as the window does and closes once ``TRACE_MIN_S`` and two
    windows have passed.  The k-th program run that starts inside the
    span therefore serves the window's k-th window, and the reduction
    counts busy and idle time from the first of them."""

    SETTLE_S = 0.2

    def __init__(self):
        import jax
        self.summary = None
        self._collects = 0
        self._ann = None
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir, profiler_options=_quiet())
        time.sleep(self.SETTLE_S)

    def open(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self._t = time.perf_counter()

    def after_collect(self):
        if self._ann is None:
            return
        self._collects += 1
        if (self._collects >= 2
                and time.perf_counter() - self._t >= TRACE_MIN_S):
            self.stop()

    def stop(self):
        import jax
        from bench import trace_reduce
        if self.dir is None:
            return
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        jax.profiler.stop_trace()
        try:
            self.summary = trace_reduce.summarize(
                trace_reduce.find_xplane(self.dir), WINDOW_SPAN, SPANS)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


def _quiet():
    """Profiler options: no Python function tracing, which would slow
    the host loop and crowd the trace; the benchmark's own spans stay."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def check(cfg, cfgmod, system, pool, done: List[Window], states, cap,
          due_abs) -> dict:
    """Compare every reply of the run with the plain reference, and the
    program's counters with what the reference says they must read."""
    ok = pool.kind == traffic.OK
    exp, exp_len, rlen = cfgmod.expected(cfg, pool, cap)
    S = system.shards
    missing = unexpected = mismatch = 0
    served_want = np.zeros(S, np.int64)
    kinds_want = np.zeros((S, 3), np.int64)
    w_ok = w_body = w_attempted = w_failed = 0
    lat = []
    for win in done:
        idx = win.rows
        live = idx >= 0
        safe = np.where(live, idx, 0)
        want = live & ok[safe]
        got = win.got
        answered = got["alive"].reshape(idx.shape) & \
            got["served"].reshape(idx.shape)
        tx_len = got["tx_len"].reshape(idx.shape)
        payload = got["tx_payload"].reshape(idx.shape + (cap,))
        both = want & answered
        wrong = both & (tx_len != exp_len[safe])
        cols = np.arange(cap)
        differs = ((payload != exp[safe])
                   & (cols < exp_len[safe][..., None])).any(-1)
        wrong |= both & differs
        miss_w = want & ~answered
        extra_w = answered & ~want
        missing += int(miss_w.sum())
        unexpected += int(extra_w.sum())
        mismatch += int(wrong.sum())
        served_want += want.sum(1)
        for k in range(3):
            kinds_want[:, k] += (live & (pool.kind[safe] == k)).sum(1)
        good = both & ~wrong
        win.ok = good.sum(1)
        if not win.measured:
            continue
        bad = miss_w | extra_w | wrong
        w_attempted += int(live.sum())
        w_failed += int(bad.sum())
        w_ok += int(good.sum())
        w_body += int(np.where(good, rlen[safe], 0).sum())
        if win.arrivals is not None:
            req_ok = good[0, :len(win.arrivals)]
            t = (win.t_reply - due_abs[win.arrivals])
            lat.append(np.where(req_ok, t, np.inf))
    if due_abs is not None:
        answered_n = sum(len(a) for a in lat)
        if answered_n < len(due_abs):       # never dispatched: gave up
            n_lost = len(due_abs) - answered_n
            missing += n_lost
            w_failed += n_lost
            w_attempted += n_lost
            lat.append(np.full(n_lost, np.inf))
    served_gap = drop_gap = 0
    for s, st in enumerate(states):
        served_gap += abs(cfgmod.served(st) - int(served_want[s]))
        drop_gap += _drop_gap(cfg, system, st, kinds_want[s])
    checks = {
        "reply_mismatch": {"value": mismatch, "limit": 0},
        "reply_missing": {"value": missing, "limit": 0},
        "reply_unexpected": {"value": unexpected, "limit": 0},
        "served_gap": {"value": served_gap, "limit": 0},
        "drop_gap": {"value": drop_gap, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    window = {"attempted": w_attempted, "failed": w_failed,
              "replies_ok": w_ok, "reply_body_bytes": w_body,
              "latency_s": np.concatenate(lat) if lat else None}
    return {"correct": correct, "checks": checks, "window": window}


def _drop_gap(cfg, system, state, kinds) -> int:
    """How far the program's drop table is from the reference's: the
    frames of each broken kind, counted at the site and reason the
    configuration states, and nothing else."""
    from repro.obs import export
    got = export.drop_table(state, system.pipeline)
    want: Dict[str, Dict[str, int]] = {}
    for kind, (site, reason) in cfg["drops"].items():
        n = int(kinds[traffic.KINDS[kind]])
        if n:
            want.setdefault(site, {})[reason] = n
    gap = 0
    for site in set(got) | set(want):
        g, w = got.get(site, {}), want.get(site, {})
        for r in set(g) | set(w):
            gap += abs(int(g.get(r, 0)) - int(w.get(r, 0)))
    return gap
