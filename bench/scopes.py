"""Device time by the program's named scopes, and the program's host
counters, for the per-layer metrics in ``bench/metrics/``.

The program names its device work with ``jax.named_scope``: each stage
of the pipeline under ``stage/<node>``, the executor's observability
blocks under ``obs/<block>``, the management commit under
``mgmt/commit``, and inside them the byte shifts and checksums under
``bytes/shift`` and ``bytes/csum``.  The scopes live in the ``op_name``
metadata of the compiled program's HLO.  A trace's op events carry the
instruction's name (``Summary.op_s``, by ``trace_reduce.op_name``), so
the map from instruction to scope comes from the compiled program: after
the window, the run's own step is lowered again for inputs of the same
shapes, which gives back the executable that ran (``program_hlo``).  A
fusion takes its root instruction's scope.  A program without scopes (one that predates them) maps nothing,
and the readers then find nothing to read.

The program's host counters (``repro.obs.host``: the ``ingress/fill``
span, the ``compile/*`` counters) are read once, before that compile, and
kept in the context for every reader.

The first reader to map the scopes also writes, on standard error, the
ten scopes with most device time (``device_scopes``) and the largest
fusions whose fused ops come from more than one stage.
"""
from __future__ import annotations

import collections
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

from bench.readers import traced_runs
from bench.trace_reduce import CONTAINERS, opcode

TOP = 10
SHIFT = "bytes/shift"
CSUM = "bytes/csum"
UNSCOPED = "unscoped"

_OUTER = re.compile(r"(?:^|/)((?:stage|obs|mgmt)/[^/]+)")
_INNER = re.compile(r"(?:^|/)(bytes/(?:shift|csum))(?=/|$)")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_META = re.compile(r'op_name="([^"]*)"(?: stack_frame_id=(\d+))?')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"(?<![\w=])%([\w.\-]+)")


def scope_path(op_name: str) -> str:
    """The program's own scopes in an ``op_name``:
    ``stage/ip_rx/bytes/shift`` out of
    ``jit(run_stream)/while/body/stage/ip_rx/bytes/shift/jit(...)/gather``;
    ``unscoped`` where it has none."""
    parts = [m.group(1) for m in _OUTER.finditer(op_name)]
    parts += [m.group(1) for m in _INNER.finditer(op_name)]
    return "/".join(parts) if parts else UNSCOPED


def outer(path: str) -> str:
    """A path's stage, observability block or commit: ``stage/ip_rx``."""
    m = _OUTER.search(path)
    return m.group(1) if m else UNSCOPED


def inner_of(path: str) -> str:
    """A path's byte operation, ``bytes/shift`` or ``bytes/csum``, or
    ``""``."""
    m = _INNER.search(path)
    return m.group(1) if m else ""


def common(paths) -> str:
    """What every path of ``paths`` shares: the outer scope, the inner
    one, both or neither (``unscoped``)."""
    outers = {outer(p) for p in paths}
    inners = {inner_of(p) for p in paths}
    parts = [x.pop() for x in (outers, inners) if len(x) == 1]
    return "/".join(p for p in parts if p and p != UNSCOPED) or UNSCOPED


def parse_hlo(text: str) -> Tuple[Dict[str, str], Dict[str, List[str]]]:
    """``({instruction: scope path}, {fusion: paths of its fused ops})``
    from an HLO module's text.

    A fusion takes its fused computation's root's path.  The chip's
    compiler rewrites some ops (its gather expansion) and leaves them a
    bare ``op_name`` (``"gather"``) that keeps only its source frame,
    which names the function (``shift_left``) but not the stage that
    called it.  Such an op takes what the scoped ops of the same
    ``stack_frame_id`` all share (``bytes/shift``), and a fusion whose
    root has no scope what its fused ops other than constants all share.
    An op left with a byte scope but no stage then takes the stage its
    users all share, else the one its operands all share: the shift's
    result goes on to the rest of the same shift."""
    own: Dict[str, str] = {}
    frame_of: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    root: Dict[str, str] = {}
    body: Dict[str, List[str]] = collections.defaultdict(list)
    by_frame: Dict[str, set] = collections.defaultdict(set)
    operands: Dict[str, List[str]] = {}
    comp = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name = m.group(2)
        operands[name] = _OPERAND.findall(line[m.end():].split(
            ", metadata=")[0])
        meta = _META.search(line)
        if meta is not None:
            path = scope_path(meta.group(1))
            own[name] = path
            if " constant(" not in line:
                body[comp].append(name)
            if m.group(1):
                root[comp] = name
            if meta.group(2):
                frame_of[name] = meta.group(2)
                if path != UNSCOPED:
                    by_frame[meta.group(2)].add(path)
        if " fusion(" in line:
            k = _CALLS.search(line)
            if k is not None:
                calls[name] = k.group(1)
    for name, path in own.items():
        if path == UNSCOPED and frame_of.get(name) in by_frame:
            own[name] = common(by_frame[frame_of[name]])
    names = dict(own)
    fused: Dict[str, List[str]] = {}
    for name, comp in calls.items():
        paths = [own[i] for i in body.get(comp, ())]
        fused[name] = paths
        top = own.get(root.get(comp, ""), UNSCOPED)
        if top == UNSCOPED:
            top = common(set(paths) - {UNSCOPED})
        names[name] = top
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for name, ops in operands.items():
        for op in ops:
            users[op].append(name)
    base = dict(names)
    for name, path in base.items():
        if outer(path) != UNSCOPED or not inner_of(path):
            continue
        for near in (users.get(name, ()), operands.get(name, ())):
            stages = {outer(base[x]) for x in near if x in base} - {UNSCOPED}
            if len(stages) == 1:
                names[name] = f"{stages.pop()}/{inner_of(path)}"
                break
    return names, fused


def scope_seconds(op_s: Dict[str, float],
                  names: Dict[str, str]) -> Dict[str, float]:
    """Device seconds by scope path, over the trace's op events (loops,
    conditionals and calls, which only hold other ops, left out)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for key, sec in op_s.items():
        if opcode(key) in CONTAINERS:
            continue
        out[names.get(key.split(" ")[0], UNSCOPED)] += sec
    return dict(out)


def device_scopes(scope_s: Dict[str, float]) -> List[Tuple[str, float]]:
    """The ten stages, observability blocks or commits (or ``unscoped``)
    with most device seconds."""
    by: Dict[str, float] = collections.defaultdict(float)
    for path, sec in scope_s.items():
        by[outer(path)] += sec
    return sorted(by.items(), key=lambda kv: -kv[1])[:TOP]


def mixed_fusions(op_s: Dict[str, float], names: Dict[str, str],
                  fused: Dict[str, List[str]]):
    """The ten fusions with most device time whose fused ops come from
    more than one stage or block: (key, seconds, root's path, paths)."""
    out = []
    for key, sec in op_s.items():
        inst = key.split(" ")[0]
        if inst not in fused:
            continue
        paths = sorted({outer(p) for p in fused[inst]} - {UNSCOPED})
        if len(paths) > 1:
            out.append((key, sec, names[inst], paths))
    return sorted(out, key=lambda r: -r[1])[:TOP]


# ---------------------------------------------------------------------------
# what the readers share, computed once per run and kept in the context


def host_counters(ctx) -> Optional[dict]:
    """The program's host counters as the window left them, or None for
    a program without them."""
    if "_host_counters" not in ctx:
        try:
            from repro.obs import host
        except ImportError:
            ctx["_host_counters"] = None
        else:
            ctx["_host_counters"] = host.counters()
    return ctx["_host_counters"]


def run_system():
    """The system the harness ran, found in ``run_cell``'s frame (the
    context holds no handle to it), or None."""
    from bench.system import Sharded, Single
    frame = sys._getframe(1)
    while frame is not None:
        found = frame.f_locals.get("system")
        if isinstance(found, (Single, Sharded)):
            return found
        frame = frame.f_back
    return None


def program_hlo(ctx) -> str:
    """The compiled text of the run's stream program.  The run's own
    jitted step, lowered again for inputs of the same shapes and
    placement, gives back the executable that ran, from JAX's caches and
    with no compile; without the run's system at hand (a test), the
    cell's system is built again from ``ctx["cfg"]``."""
    from bench import harness
    host_counters(ctx)
    system = run_system()
    if system is None:
        _, cfgmod = harness.load_config(ctx["cfg"]["name"])
        system = cfgmod.build(ctx["cfg"])
    state = system.init_state()
    p, l = system.put(system.new_arena())
    return system.step.lower(state, p, l).compile().as_text()


def scope_s(ctx) -> Optional[Dict[str, float]]:
    """Device seconds by scope path in the traced runs, or None where
    the trace or the program's scopes are missing."""
    if "_scope_s" in ctx:
        return ctx["_scope_s"]
    ctx["_scope_s"] = None
    trace = ctx.get("trace")
    if trace is None or not traced_runs(ctx):
        return None
    names, fused = parse_hlo(program_hlo(ctx))
    if all(path == UNSCOPED for path in names.values()):
        return None
    found = scope_seconds(trace.op_s, names)
    ctx["_scope_s"] = found
    print("device_scopes " + json.dumps(device_scopes(found)),
          file=sys.stderr)
    print("mixed_fusions " + json.dumps(
        mixed_fusions(trace.op_s, names, fused)), file=sys.stderr)
    print("scope_s " + json.dumps(found), file=sys.stderr, flush=True)
    return found


def share(ctx, inner: Optional[str] = None,
          block: Optional[str] = None) -> Optional[float]:
    """Device time of the ops under ``inner`` (``bytes/shift``) or under
    an outer scope starting ``block`` (``obs/``), over the device time of
    the whole program runs in the trace, summed over the chips, in %."""
    found = scope_s(ctx)
    if found is None:
        return None
    whole = sum(sec for _, _, sec in traced_runs(ctx))
    if whole <= 0:
        return None
    part = sum(sec for path, sec in found.items()
               if (inner is not None and inner_of(path) == inner)
               or (block is not None and outer(path).startswith(block)))
    return 100.0 * part / whole
