"""From a profiler trace of the measured window to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists of ``(name, start_ns, duration_ns)`` per plane and line;
``reduce_events`` then works on those alone:

  * busy time: the union of the intervals in which each device ran a
    program, an op or an asynchronous copy, clipped to the window the
    benchmark's own ``bench_window`` host span marks, from the device's
    first run of the stream program in it: before that run the pipeline
    is still empty, waiting for the host's first window;
  * program runs: each device's runs of the stream program (the
    ``XLA Modules`` events that held it longest) that started and ended
    inside the window, so a reader can tie them to the benchmark's windows;
  * op time: the summed durations of the op events inside those runs, by
    a short name (``op_name``), from which a kernel's time is read;
  * idle time: the stretches of that window in which a device ran no op,
    split by what the host was doing meanwhile, as the benchmark's own
    host spans say (``fill``, ``put``, ``dispatch``, ``readback``), and
    ``none`` where it was in none of them.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

Event = Tuple[str, float, float]             # name, start_ns, duration_ns
Planes = Dict[str, Dict[str, List[Event]]]

DEVICE_PREFIX = "/device:"
OP_LINE = "XLA Ops"                          # a device's ops, one by one
# lines whose events are the device at work: whole programs, their ops,
# and the asynchronous copies that run beside them
BUSY_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
TOP = 10


_HLO = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KERNEL = re.compile(r'"kernel_name"\s*:\s*"([^"]+)"|kernel_name=([\w.\-]+)')
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """A short name for an op event, whose name on a TPU is the whole HLO
    instruction: ``<instruction> <opcode>``, the custom call's target and
    kernel name where it has them."""
    m = _HLO.match(text)
    if not m:
        return text[:120]
    name = f"{m.group(1)} {m.group(2)}"
    t = _TARGET.search(text)
    if t:
        name += f" {t.group(1)}"
    k = _KERNEL.search(text)
    if k:
        name += f" {k.group(1) or k.group(2)}"
    return name


def opcode(short_name: str) -> str:
    parts = short_name.split(" ")
    return parts[1] if len(parts) > 1 else ""


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Planes:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Planes = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of (n, 2) [start, end) intervals as sorted disjoint ones."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    group_end = np.append(np.flatnonzero(new)[1:] - 1, len(iv) - 1)
    return np.stack([starts, reach[group_end]], axis=1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals
    c = np.stack([np.maximum(intervals[:, 0], lo),
                  np.minimum(intervals[:, 1], hi)], axis=1)
    return c[c[:, 1] > c[:, 0]]


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The stretches of [lo, hi) that the merged ``busy`` leaves free."""
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def length(intervals: np.ndarray) -> float:
    return float((intervals[:, 1] - intervals[:, 0]).sum())


def common_length(a: np.ndarray, b: np.ndarray) -> float:
    """How long the merged ``a`` and the merged ``b`` overlap:
    |a| + |b| - |a or b|."""
    if len(a) == 0 or len(b) == 0:
        return 0.0
    return length(a) + length(b) - length(merge(np.concatenate([a, b])))


def _intervals(events: Sequence[Event]) -> np.ndarray:
    if not events:
        return np.zeros((0, 2))
    a = np.asarray([(s, s + d) for _, s, d in events], np.float64)
    return a


@dataclasses.dataclass
class Summary:
    window_s: float                   # mean over devices, from the first run
    busy_s: float                     # mean over devices
    busy_s_total: float               # summed over devices
    devices: int
    op_s: Dict[str, float]            # summed over devices, by op_name
    idle_gaps: List[Tuple[str, float]]
    # per device, the stream program's runs that started and ended inside
    # the window, the last run recorded left out: (rank, seconds), rank 0
    # being the first run to start in it; ops count only inside these runs
    # when there are any
    runs: List[List[Tuple[int, float]]] = dataclasses.field(
        default_factory=list)

    def breakdown(self) -> dict:
        """The ops that took most device time (loops and conditionals,
        which only hold other ops, left out), and the idle time by what
        the host was doing."""
        ops = sorted(((k, v) for k, v in self.op_s.items()
                      if opcode(k) not in CONTAINERS),
                     key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:TOP]]}


def device_lines(planes: Planes) -> Dict[str, Dict[str, List[Event]]]:
    """Each device plane that holds op events, with its lines."""
    return {name: lines for name, lines in sorted(planes.items())
            if name.startswith(DEVICE_PREFIX) and lines.get(OP_LINE)}


def reduce_events(planes: Planes, window_span: str,
                  span_names: Sequence[str]) -> Summary:
    host = [ev for pname, lines in planes.items()
            if not pname.startswith(DEVICE_PREFIX)
            for evs in lines.values() for ev in evs]
    win = [ev for ev in host if ev[0] == window_span]
    devs = device_lines(planes)
    if not devs:
        raise ValueError("the trace holds no device op events")
    if win:
        lo, hi = win[-1][1], win[-1][1] + win[-1][2]
    else:
        allv = np.concatenate([_intervals(d[OP_LINE]) for d in devs.values()])
        lo, hi = float(allv[:, 0].min()), float(allv[:, 1].max())
    spans = {n: merge(clip(_intervals([e for e in host if e[0] == n]),
                           lo, hi)) for n in span_names}
    op_ns: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    short: Dict[str, str] = {}
    busy, runs, spanned = [], [], []
    program = _main_program(devs)
    for lines in devs.values():
        mods = sorted(ev[1:] for ev in lines.get("XLA Modules", ())
                      if ev[0] == program)
        first = [s for s, _ in mods if lo < s < hi]
        start = first[0] if first else lo
        work = [ev for ln in BUSY_LINES for ev in lines.get(ln, ())]
        m = merge(clip(_intervals(work), start, hi))
        busy.append(length(m))
        spanned.append(hi - start)
        # the last run recorded may be cut short where the device trace
        # stops, and its end need not fall after the host's window span
        inside = [(s, s + d) for s, d in mods[:-1] if s > lo]
        whole = [(r, s, e) for r, (s, e) in enumerate(inside) if e < hi]
        runs.append([(r, (e - s) * 1e-9) for r, s, e in whole])
        within = (np.asarray([(s, e) for _, s, e in whole])
                  if whole else np.asarray([(lo, hi)]))
        ops = lines[OP_LINE]
        iv = _intervals(ops)
        k = np.searchsorted(within[:, 0], iv[:, 0], "right") - 1
        keep = (k >= 0) & (iv[:, 1] <= within[np.maximum(k, 0), 1])
        ids = {}
        which = np.fromiter((ids.setdefault(e[0], len(ids)) for e in ops),
                            np.int64, len(ops))
        spent = np.bincount(which[keep], weights=iv[keep, 1] - iv[keep, 0],
                            minlength=len(ids))
        for name, i in ids.items():
            if spent[i] > 0:
                if name not in short:
                    short[name] = op_name(name)
                op_ns[short[name]] += float(spent[i])
        g = gaps(m, start, hi)
        free = length(g)
        for name in span_names:
            covered = common_length(g, spans[name])
            idle[name] += covered
            free -= covered
        idle["none"] += max(free, 0.0)
    n = len(busy)
    return Summary(
        window_s=sum(spanned) / n * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        busy_s_total=sum(busy) * 1e-9,
        devices=n,
        op_s={k: v * 1e-9 for k, v in op_ns.items()},
        idle_gaps=sorted(((k, v * 1e-9 / n) for k, v in idle.items()),
                         key=lambda kv: -kv[1]),
        runs=runs)


def _main_program(devs) -> str:
    """The program that held the devices longest: the stream program."""
    total: Dict[str, float] = collections.defaultdict(float)
    for lines in devs.values():
        for name, _, d in lines.get("XLA Modules", ()):
            total[name] += d
    return max(total, key=total.get) if total else ""


def summarize(path: str, window_span: str,
              span_names: Sequence[str]) -> Summary:
    return reduce_events(load(path), window_span, span_names)
