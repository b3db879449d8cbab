"""Topology-compiled stack executor.

The paper's stacks are *configurations*: protocol and application elements
are tiles over the NoC, and the processing graph is whatever the declared
routes say — adding NAT to the TCP path, IP-in-IP to the UDP path, or a
new app replica is a topology edit, never a code edit.  This module makes
the Python runtime behave the same way: :class:`StackCompiler` takes any
validated :class:`TopologyConfig` and emits one jittable batch pipeline.

Compilation steps:

  1. tiles are grouped into execution nodes (app replicas — tiles whose
     kind is ``app:<name>`` — collapse into one dispatch group, mirroring
     the paper's scale-out sets);
  2. the route entries define a DAG over nodes; nodes are topologically
     ordered (stable in declaration order, so replica dispatch matches the
     builder's app order);
  3. each node's kind is bound to a *tile function* from the registry
     (``register_tile``); per-tile state threads through one state pytree;
  4. each packet's path is predicated by the route-match fields
     (``ethertype``, ``ip_proto``, ``udp_port``, …): a packet "arrives" at
     a node iff some in-edge's source succeeded on it AND the route key
     matches — the Python analog of the paper's CAM routing, with no
     hardcoded per-protocol branches anywhere;
  5. every node gets a :class:`telemetry.RingLog` in the state pytree and
     the compiled pipeline appends one counter row per batch per node
     (packets-in, drops, a compile-time NoC latency estimate from
     ``noc.chain_latency_cycles``) — diagnostics come for free on every
     path.

Tile function contract::

    @register_tile("my_kind", init=my_init)          # my_init(ctx) -> dict
    def my_tile(state, carrier, pred, ctx):
        ...
        return state, carrier, ok        # ok: (B,) bool or None (all pass)

``state`` is the full stack state dict (tile functions own documented
slices of it: ``conn`` for TCP, ``nat`` for NAT tables, ``dispatch`` /
``apps`` for app groups).  ``carrier`` is the per-batch value dict
(payload/length/meta plus direction-specific keys); functions mutate a
fresh shallow copy provided by the executor.  ``pred`` is the node's
arrival predicate.  ``ctx`` is a :class:`TileContext`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import deadlock, routing, telemetry
from repro.core.noc import chain_latency_cycles
from repro.core.topology import RouteEntry, TileDecl, TopologyConfig
from repro.obs import flight, postcard, reasons, series, slo

# reference payload for the per-tile NoC latency estimate (the paper's
# latency measurement uses 64-byte messages)
REF_PAYLOAD_BYTES = 64


class CompileError(ValueError):
    pass


# ---------------------------------------------------------------------------
# tile-function registry


@dataclasses.dataclass
class TileSpec:
    fn: Callable
    init: Optional[Callable] = None     # (ctx) -> state-dict contribution
    alive: bool = False                 # RX parse tile: pred & ok feeds the
                                        # chain's "alive" mask
    rewrites: Tuple[str, ...] = ()      # meta fields this kind re-parses
                                        # (pruning soundness: see
                                        # StackCompiler._prune_dead)


TILE_REGISTRY: Dict[str, TileSpec] = {}


def register_tile(kind: str, init: Optional[Callable] = None,
                  alive: bool = False, rewrites: Tuple[str, ...] = ()):
    """Decorator binding a tile kind to its jittable tile function.  Pass
    alive=True for RX-side parse tiles whose success gates packet
    validity (their pred & ok becomes carrier['alive'] downstream).
    `rewrites` names the route-match meta fields the tile (re)writes —
    a duplicated parse tile (the paper's repeated-header pattern) makes
    that field runtime-dependent, which disables dead-stage pruning on
    it."""
    def deco(fn):
        TILE_REGISTRY[kind] = TileSpec(fn=fn, init=init, alive=alive,
                                       rewrites=tuple(rewrites))
        return fn
    return deco


def resolve_kind(kind: str) -> TileSpec:
    """Exact kind first, then the family before ':' (app:echo -> app)."""
    if kind in TILE_REGISTRY:
        return TILE_REGISTRY[kind]
    fam = kind.split(":", 1)[0]
    if fam in TILE_REGISTRY:
        return TILE_REGISTRY[fam]
    raise CompileError(f"no tile function registered for kind {kind!r} "
                       f"(known: {sorted(TILE_REGISTRY)})")


@dataclasses.dataclass
class TileContext:
    name: str                   # node name (tile name / app group name)
    kind: str
    members: List[TileDecl]     # 1 entry for plain tiles, N for app groups
    binding: Any                # e.g. the AppDecl for app groups
    options: Dict[str, Any]     # compiler-level options (local_ip, ...)
    lat_cycles: int             # NoC latency estimate from the ingress
    index: int                  # execution position
    pipe: Any = None            # pipeline-level meta (order/groups/tables) —
                                # management tiles address peers through it


# ---------------------------------------------------------------------------
# route-match predicates (the CAM lookup, paper §4.2)

_MATCH_FIELD = {"ethertype": "ethertype", "ip_proto": "ip_proto",
                "udp_port": "dst_port", "tcp_port": "dst_port",
                "rpc_msg": "msg_type"}


def _match_pred(route: RouteEntry, carrier, n):
    """Per-packet bool for one route entry, evaluated on the live meta."""
    field = _MATCH_FIELD.get(route.match)
    if field is None or route.key is None:     # const / rr / flow_hash / vip
        return jnp.ones((n,), bool)            # wildcard: dispatch decides
    return carrier["meta"][field] == route.key


# ---------------------------------------------------------------------------
# nodes + compiler


@dataclasses.dataclass
class _Node:
    name: str
    kind: str
    members: List[TileDecl]
    index: int


def deep_merge(dst: dict, src: dict) -> dict:
    for k, v in src.items():
        if k in dst and isinstance(dst[k], dict) and isinstance(v, dict):
            deep_merge(dst[k], v)
        else:
            dst[k] = v
    return dst


class StackCompiler:
    """Compiles a TopologyConfig into executable pipelines.

    bindings: extra per-node configuration, keyed by node name (the app
    group name for ``app:*`` tiles).  options: stack-level settings read
    by tile init functions (``local_ip``, ``max_conns``, ``nat_entries``,
    ``outer_src``/``outer_dst`` for IP-in-IP, ...).
    """

    def __init__(self, topo: TopologyConfig,
                 bindings: Optional[Dict[str, Any]] = None,
                 options: Optional[Dict[str, Any]] = None,
                 check_deadlock: bool = True,
                 noc: str = "data"):
        errs = topo.validate()
        if errs:
            raise CompileError("invalid topology:\n" + "\n".join(errs))
        if check_deadlock:
            deadlock.assert_deadlock_free(topo)
        self.topo = topo
        self.bindings = bindings or {}
        self.options = options or {}

        # ---- replica groups (core.scaleout.replicate on non-app kinds) -
        # validated here so an un-lowerable group fails loudly at compiler
        # construction, naming the group — never silently mis-routing
        self._rgroups: Dict[str, Dict] = {}
        member_group: Dict[str, str] = {}
        for gname, g in getattr(topo, "replica_groups", {}).items():
            self._check_replica_group(gname, g)
            if g.get("noc", "data") != noc:
                continue
            self._rgroups[gname] = g
            for m in g["members"]:
                member_group[m] = gname

        # ---- group tiles into nodes -----------------------------------
        self.nodes: Dict[str, _Node] = {}
        self._node_of: Dict[str, str] = {}
        for t in topo.tiles_on(noc):
            if t.kind.startswith("app:"):
                nname = t.kind.split(":", 1)[1]
            else:
                nname = member_group.get(t.name, t.name)
            node = self.nodes.get(nname)
            if node is None:
                self.nodes[nname] = _Node(nname, t.kind, [t],
                                          len(self.nodes))
            else:
                if node.kind != t.kind:
                    raise CompileError(
                        f"group {nname!r} mixes kinds {node.kind!r} and "
                        f"{t.kind!r}")
                node.members.append(t)
            self._node_of[t.name] = nname
        for gname in self._rgroups:
            # upstream CAM entries still target the group name
            self._node_of.setdefault(gname, gname)

        # ---- route edges between nodes --------------------------------
        # replica members carry identical route clones — dedupe so the
        # group node gets each logical edge once (table slots included)
        self.edges: List[Tuple[str, str, RouteEntry]] = []
        seen_edges = set()
        for t in topo.tiles_on(noc):
            for r in t.routes:
                src = self._node_of.get(t.name)
                dst = self._node_of.get(r.next_tile)
                if src is None or dst is None or src == dst:
                    continue                       # intra-group / other noc
                ek = (src, dst, r.match, r.key)
                if ek in seen_edges:
                    continue
                seen_edges.add(ek)
                self.edges.append((src, dst, r))

    # kinds whose state/behavior is structurally singleton: lowering N
    # copies behind one dispatch stage would be meaningless or wrong
    _UNREPLICABLE = ("mgmt", "controller", "ctrl_in", "mgmt_ep",
                     "int_mirror", "watchdog")

    def _check_replica_group(self, gname: str, g: Dict) -> None:
        members = g.get("members") or []
        if not members:
            raise CompileError(
                f"replica group {gname!r} has no members — nothing to "
                f"lower behind the dispatch stage")
        kind = g.get("kind", "")
        if kind in self._UNREPLICABLE or kind.startswith("app:"):
            raise CompileError(
                f"replica group {gname!r} replicates kind {kind!r}, which "
                f"cannot be lowered (management/structural tiles are "
                f"singletons; app:* tiles scale via AppDecl.n_replicas)")
        policy = g.get("policy")
        if policy not in ("flow_hash", "round_robin", "port_match"):
            raise CompileError(
                f"replica group {gname!r} has un-lowerable dispatch "
                f"policy {policy!r} (expected flow_hash, round_robin or "
                f"port_match)")
        if policy == "port_match" and g.get("base_port") is None:
            raise CompileError(
                f"replica group {gname!r} uses port_match dispatch but "
                f"declares no base_port (replicate(..., base_port=...))")
        for m in members:
            if not self.topo.has_tile(m):
                raise CompileError(
                    f"replica group {gname!r} member {m!r} is not a "
                    f"declared tile")
            mk = self.topo.tile(m).kind
            if mk != kind:
                raise CompileError(
                    f"replica group {gname!r} mixes kinds {kind!r} and "
                    f"{mk!r} (member {m!r})")

    # ---- ordering --------------------------------------------------------
    def _reachable(self, ingress: str) -> List[str]:
        seen = {ingress}
        frontier = [ingress]
        while frontier:
            cur = frontier.pop()
            for s, d, _ in self.edges:
                if s == cur and d not in seen:
                    seen.add(d)
                    frontier.append(d)
        return sorted(seen, key=lambda n: self.nodes[n].index)

    def _topo_order(self, names: Sequence[str]) -> List[str]:
        names = set(names)
        indeg = {n: 0 for n in names}
        for s, d, _ in self.edges:
            if s in names and d in names:
                indeg[d] += 1
        order: List[str] = []
        ready = sorted([n for n, d in indeg.items() if d == 0],
                       key=lambda n: self.nodes[n].index)
        while ready:
            cur = ready.pop(0)
            order.append(cur)
            for s, d, _ in self.edges:
                if s == cur and d in indeg:
                    indeg[d] -= 1
                    if indeg[d] == 0:
                        ready.append(d)
            ready.sort(key=lambda n: self.nodes[n].index)
        if len(order) != len(names):
            cyc = sorted(names - set(order))
            raise CompileError(f"route graph has a cycle through {cyc}")
        return order

    def _latency_estimates(self, ingress: str,
                           names: Sequence[str]) -> Dict[str, int]:
        """Compile-time NoC latency (cycles) from the ingress tile to each
        node, along the shortest route-graph path (BFS)."""
        parent: Dict[str, Optional[str]] = {ingress: None}
        frontier = [ingress]
        while frontier:
            nxt = []
            for cur in frontier:
                for s, d, _ in self.edges:
                    if s == cur and d not in parent:
                        parent[d] = cur
                        nxt.append(d)
            frontier = nxt
        out = {}
        for n in names:
            path, cur = [], n
            while cur is not None:
                path.append(cur)
                cur = parent.get(cur)
            coords = [self.nodes[p].members[0].coord for p in reversed(path)]
            out[n] = chain_latency_cycles(coords, REF_PAYLOAD_BYTES)
        return out

    # ---- dead-stage pruning ----------------------------------------------
    # Route keys on ethertype / ip_proto are *structural*: a packet can
    # only carry one value per header field, so an edge keyed on a value
    # that contradicts what every upstream path already committed to can
    # never fire, and a node whose in-edges are all dead is untraceable
    # garbage — prune it before tracing instead of compiling a stage whose
    # predicate is constant-false.  Port-keyed routes (udp_port/tcp_port)
    # are never pruned: those CAMs are the runtime-rewritable surface
    # (ROUTE_SET), so their reachability is a runtime question.
    _STATIC_MATCH = ("ethertype", "ip_proto")

    def _prune_dead(self, start: str,
                    order: Sequence[str]) -> Tuple[List[str], List[str]]:
        """Constraint propagation over the route DAG: for each node, the
        set of values each static field can still hold on arriving
        packets (missing field = unconstrained).  Joins union field-wise
        (a conservative over-approximation — pruning only when *every*
        path contradicts).

        Soundness under repeated headers: predicates evaluate the *live*
        carrier meta, and a duplicated parse tile (e.g. the inner ip_rx
        behind an IP-in-IP decap, paper §3.5) rewrites its field for the
        whole batch.  A field rewritten by more than one compiled node is
        therefore runtime-dependent and exempt from pruning entirely —
        tile kinds declare what they rewrite via ``register_tile(...,
        rewrites=...)``."""
        def join(a, b):
            return {f: a[f] | b[f] for f in set(a) & set(b)}

        writers: Dict[str, int] = {}
        for n in order:
            for f in resolve_kind(self.nodes[n].kind).rewrites:
                writers[f] = writers.get(f, 0) + 1
        static = tuple(f for f in self._STATIC_MATCH
                       if writers.get(f, 0) <= 1)

        names = set(order)
        feasible: Dict[str, Dict[str, set]] = {start: {}}
        for n in order:
            if n == start:
                continue
            merged = None
            for s, d, r in self.edges:
                if d != n or s not in names or s not in feasible:
                    continue
                cs = feasible[s]
                if r.match in static and r.key is not None:
                    vals = cs.get(r.match)
                    if vals is not None and r.key not in vals:
                        continue               # edge contradicts upstream
                    cs = dict(cs)
                    cs[r.match] = {r.key}
                merged = cs if merged is None else join(merged, cs)
            if merged is not None:
                feasible[n] = merged
        return ([n for n in order if n in feasible],
                [n for n in order if n not in feasible])

    def _is_trunk(self, ingress: str, names, node: str) -> bool:
        """True when every packet path from the ingress passes through
        `node` (route-DAG post-dominance): no sink stays reachable once the
        node is removed.  A trunk alive-tile *gates* the whole stack (its
        pred & ok replaces the alive mask, like the hand-written chains);
        a branch alive-tile only judges the packets routed through it."""
        names = set(names)
        sinks = {n for n in names
                 if not any(s == n and d in names for s, d, _ in self.edges)}
        seen = {ingress} if ingress != node else set()
        frontier = list(seen)
        while frontier:
            cur = frontier.pop()
            for s, d, _ in self.edges:
                if s == cur and d in names and d != node and d not in seen:
                    seen.add(d)
                    frontier.append(d)
        return not (seen & sinks)

    # ---- compilation -----------------------------------------------------
    def compile(self, ingress: str) -> "CompiledPipeline":
        """Pipeline over every node reachable from `ingress` (a tile name)."""
        if ingress not in self._node_of:
            raise CompileError(f"unknown ingress tile {ingress!r}")
        start = self._node_of[ingress]
        names = self._reachable(start)
        order = self._topo_order(names)
        order, pruned = self._prune_dead(start, order)
        names = list(order)
        lats = self._latency_estimates(start, names)
        index_of = {n: i for i, n in enumerate(order)}

        # runtime route tables (the paper's runtime-rewritable CAMs): every
        # keyed route entry becomes a slot in a per-(source, match-space)
        # table held in state, so the control plane can rewrite dispatch
        # without recompiling.  Values are execution-node indices.
        table_entries: Dict[str, List[Tuple[int, int]]] = {}
        for s, d, r in self.edges:
            if (s in index_of and d in index_of and r.key is not None
                    and r.match in _MATCH_FIELD):
                table_entries.setdefault(f"{s}:{r.match}", []).append(
                    (r.key, index_of[d]))

        pipe_meta = {
            "order": order,
            # dispatch groups the management HEALTH_SET path addresses:
            # app groups AND lowered replica groups, in execution order
            "groups": [n for n in order
                       if self.nodes[n].kind.startswith("app:")
                       or n in self._rgroups],
            "tables": sorted(table_entries),
        }

        stages = []
        for i, n in enumerate(order):
            node = self.nodes[n]
            spec = resolve_kind(node.kind)
            if n in self._rgroups:
                # RSS lowering: the inner tile fn runs once over the whole
                # batch (replicas = batched lanes); the dispatch policy
                # table rides in the scan carry as runtime state
                g = self._rgroups[n]
                spec = dataclasses.replace(
                    spec,
                    fn=_replica_group_fn(spec.fn, n, g["policy"],
                                         g.get("base_port")),
                    init=_replica_group_init(spec.init, n,
                                             len(g["members"])))
            binding = self.bindings.get(n, self.bindings.get(node.kind))
            ctx = TileContext(name=n, kind=node.kind, members=node.members,
                              binding=binding, options=self.options,
                              lat_cycles=lats[n], index=i, pipe=pipe_meta)
            in_edges = [(s, r) for s, d, r in self.edges
                        if d == n and s in index_of]
            trunk = spec.alive and self._is_trunk(start, names, n)
            stages.append((node, spec, ctx, in_edges, trunk))
        return CompiledPipeline(start, stages, table_entries, pipe_meta,
                                pruned=pruned)


class CompiledPipeline:
    """One jittable executor: run(state, carrier) -> (state, carrier) per
    batch, or run_stream(state, payloads, lengths) for N device-resident
    batches under one lax.scan."""

    # carrier keys worth stacking out of a streamed run (whichever exist)
    STREAM_OUT_KEYS = ("tx_payload", "tx_len", "alive", "info", "tcp_resps",
                       "pc_payload", "pc_len", "pc_valid",
                       "alert_payload", "alert_len", "alert_valid")

    def __init__(self, ingress: str, stages, table_entries=None,
                 pipe_meta=None, pruned=None):
        self.ingress = ingress
        self.stages = stages
        self.table_entries = table_entries or {}
        self.pruned = list(pruned or [])
        self.pipe_meta = pipe_meta or {"order": self.order, "groups": [],
                                       "tables": []}
        self._index = {node.name: i
                       for i, (node, *_) in enumerate(self.stages)}
        # static per-node columns of the fused telemetry row block
        self._lat_cycles = jnp.asarray(
            [ctx.lat_cycles for _, _, ctx, *_ in self.stages], jnp.int32)
        self._node_idx = jnp.arange(len(self.stages), dtype=jnp.int32)
        # push-mode observability taps (repro.obs.{postcard,slo}): the
        # tiles are structural, the executor packs their egress frames
        local_ip = 0
        if self.stages:
            local_ip = int(self.stages[0][2].options.get("local_ip") or 0)
        self._mirror_cfg = None
        self._watchdog_cfg = None
        for node, _, ctx, *_ in self.stages:
            if node.kind == "int_mirror":
                self._mirror_cfg = postcard.tile_cfg(
                    node.members[0].params, local_ip)
            elif node.kind == "watchdog":
                self._watchdog_cfg = postcard.tile_cfg(
                    node.members[0].params, local_ip)

    @property
    def order(self) -> List[str]:
        return [node.name for node, *_ in self.stages]

    def summary(self) -> str:
        lines = []
        for node, _, ctx, in_edges, _trunk in self.stages:
            srcs = ", ".join(f"{s}[{r.match}"
                             f"{'' if r.key is None else '=' + hex(r.key)}]"
                             for s, r in in_edges) or "(ingress)"
            lines.append(f"{ctx.index:2d} {node.name:<12} kind={node.kind:<12}"
                         f" lat~{ctx.lat_cycles}cyc <- {srcs}")
        return "\n".join(lines)

    # ---- state -----------------------------------------------------------
    def init_state(self, with_telemetry: bool = True,
                   log_entries: int = telemetry.PIPE_LOG_ENTRIES,
                   with_obs: bool = True) -> Dict[str, Any]:
        st: Dict[str, Any] = {}
        for node, spec, ctx, *_ in self.stages:
            if spec.init is not None:
                deep_merge(st, spec.init(ctx))
        if self.table_entries:
            deep_merge(st, {"routes": {
                t: routing.make_table(ents)
                for t, ents in self.table_entries.items()}})
        if with_telemetry:
            deep_merge(st, {"telemetry": {
                "step": jnp.zeros((), jnp.int32),
                "nodes": telemetry.make_node_log(len(self.stages),
                                                 log_entries),
                "logs": {},
                "drops": telemetry.make_drop_table(len(self.stages),
                                                   reasons.NUM_REASONS),
            }})
            if with_obs:
                st["telemetry"]["obs"] = flight.make_obs(len(self.stages))
                st["telemetry"]["series"] = series.make_series(
                    len(self.stages))
        # logs served together over LOG_READ are stacked: every log must
        # share one ring depth (tile inits contribute extra logs, e.g.
        # tcp_cc.*, at telemetry.PIPE_LOG_ENTRIES) — reject a mismatch
        # here instead of crashing inside the compiled mgmt tile
        logs = st.get("telemetry", {}).get("logs", {})
        depths = {lg.entries.shape[0] for lg in logs.values()}
        if "nodes" in st.get("telemetry", {}):
            depths.add(st["telemetry"]["nodes"].entries.shape[0])
        if len(depths) > 1:
            raise ValueError(
                f"telemetry logs mix ring depths {sorted(depths)}; use "
                f"log_entries={telemetry.PIPE_LOG_ENTRIES} "
                f"(telemetry.PIPE_LOG_ENTRIES) when tile-contributed logs "
                f"are present")
        return st

    # ---- telemetry access ------------------------------------------------
    def node_log(self, state, name: str) -> telemetry.RingLog:
        """One node's counter rows out of the stacked node log, as an
        ordinary RingLog view (for `telemetry.latest` / `entry_at`)."""
        return telemetry.node_view(state["telemetry"]["nodes"],
                                   self._index[name])

    def node_logs(self, state) -> Dict[str, telemetry.RingLog]:
        return {n: self.node_log(state, n) for n in self.order}

    # ---- execution -------------------------------------------------------
    def run(self, state: Dict[str, Any], carrier: Dict[str, Any],
            with_telemetry: bool = True):
        """One batch through the chain.  ``telemetry["nodes"]`` (the
        stacked per-node counter log) is owned by the pipeline whose
        ``init_state`` created it — a pipeline running against another
        pipeline's state (e.g. the TCP TX build chain, whose returned
        state is discarded) must pass ``with_telemetry=False``."""
        state = dict(state)
        carrier = dict(carrier)
        carrier.setdefault("meta", {})
        carrier.setdefault("info", {})
        n = carrier["payload"].shape[0]

        telem = state.get("telemetry") if with_telemetry else None
        if telem is not None:
            src = state["telemetry"]
            telem = {"step": src["step"] + 1, "logs": dict(src["logs"])}
            for k in ("nodes", "drops", "series"):
                if k in src:
                    telem[k] = src[k]
            if "obs" in src:
                telem["obs"] = dict(src["obs"])
            state["telemetry"] = telem
        count_nodes = telem is not None and "nodes" in telem
        count_drops = telem is not None and "drops" in telem
        obs = telem.get("obs") if telem is not None else None

        routes_rt = state.get("routes")
        pkts_in: List[jnp.ndarray] = []
        drops: List[jnp.ndarray] = []
        bytes_l: List[jnp.ndarray] = []
        drop_blocks: List[jnp.ndarray] = []
        enters: List[jnp.ndarray] = []
        exits: List[jnp.ndarray] = []
        visits: List[jnp.ndarray] = []
        first_reason = jnp.zeros((n,), jnp.int32)
        zero_reason = jnp.zeros((n,), jnp.int32)
        ok_of: Dict[str, jnp.ndarray] = {}
        taken: Dict[str, jnp.ndarray] = {}     # src -> rows an out-edge took
        for node, spec, ctx, in_edges, trunk in self.stages:
            if not in_edges:                       # ingress / chain root
                pred = jnp.ones((n,), bool)
            else:
                pred = jnp.zeros((n,), bool)
                for src, route in in_edges:
                    tname = f"{src}:{route.match}"
                    if (route.key is not None and route.match in _MATCH_FIELD
                            and routes_rt is not None
                            and tname in routes_rt):
                        # live CAM lookup: the control plane can rewrite
                        # this table between batches (paper §4.2)
                        field = carrier["meta"][_MATCH_FIELD[route.match]]
                        nxt = routes_rt[tname].lookup(
                            field.astype(jnp.int32))
                        hit = nxt == self._index[node.name]
                    else:
                        hit = _match_pred(route, carrier, n)
                    pred = pred | (ok_of[src] & hit)
                    taken[src] = taken.get(src, False) | hit
            carrier = dict(carrier)
            carrier["drop_reason"] = zero_reason   # tiles overwrite per row
            stage_len = carrier["length"]          # view before the tile
            state, carrier, ok = spec.fn(state, carrier, pred, ctx)
            ok_of[node.name] = pred & ok if ok is not None else pred
            if spec.alive:
                if trunk:      # gates all traffic: alive = arrived & ok
                    carrier["alive"] = ok_of[node.name]
                else:          # branch tile: judge only its own packets
                    prev = carrier.get("alive", jnp.ones((n,), bool))
                    carrier["alive"] = jnp.where(pred, ok_of[node.name],
                                                 prev)
            if count_nodes:
                pkts_in.append(pred.sum(dtype=jnp.int32))
                drops.append((pred & ~ok_of[node.name]).sum(dtype=jnp.int32))
                bytes_l.append(jnp.where(pred, stage_len,
                                         0).sum().astype(jnp.int32))
            if count_drops or obs is not None:
                # drop attribution: hard drops (arrived & failed) plus
                # soft drops (tile set a reason but kept the packet alive,
                # e.g. an app error reply); hard drops with no tile-
                # supplied code fall back to UNSPEC
                reason = carrier["drop_reason"]
                hard = pred & ~ok_of[node.name]
                counted = hard | (pred & (reason > 0))
                reason = jnp.where(counted & (reason == 0),
                                   reasons.UNSPEC, reason)
                if count_drops:
                    drop_blocks.append(telemetry.reason_counts(
                        reason, counted, reasons.NUM_REASONS))
                if obs is not None:
                    first_reason = jnp.where(
                        (first_reason == 0) & counted, reason, first_reason)
                    # per-frame stage occupancy proxy: static NoC latency
                    # estimate + arrival-queue position within the batch
                    q = jnp.cumsum(pred.astype(jnp.int32)) - 1
                    enters.append(ctx.lat_cycles + q)
                    exits.append(ctx.lat_cycles + q + 1)
                    visits.append(pred)

        # packets a routing stage passed but no out-edge took have no
        # route: attributed to that stage (the ingress is exempt — the
        # zero-length padding rows of a batch match no route there)
        if count_drops or obs is not None:
            for src, hit in taken.items():
                i = self._index[src]
                if not self.stages[i][3]:
                    continue
                lost = ok_of[src] & ~hit
                if count_drops:
                    drop_blocks[i] = drop_blocks[i] + telemetry.reason_counts(
                        jnp.full((n,), reasons.NO_ROUTE, jnp.int32), lost,
                        reasons.NUM_REASONS)
                if obs is not None:
                    first_reason = jnp.where((first_reason == 0) & lost,
                                             reasons.NO_ROUTE, first_reason)

        # ---- fused telemetry: ONE stacked row write for the whole batch --
        # (the per-stage masked appends collapsed into a single
        # (num_nodes, LOG_WIDTH) scatter; readback therefore serves rows
        # *through the previous batch* — the batch's own row lands when it
        # completes, like a telemetry DMA at pipeline egress)
        if count_nodes:
            rows = telemetry.counter_rows(
                telem["step"], jnp.stack(pkts_in), jnp.stack(drops),
                self._lat_cycles, self._node_idx)
            telem["nodes"] = telemetry.append_stacked(telem["nodes"], rows)
        if count_drops and drop_blocks:
            # ONE fused (num_nodes, NUM_REASONS) add per batch — same
            # egress-DMA discipline as the counter rows above, so DROP_READ
            # serves totals *through the previous batch*
            telem["drops"] = telem["drops"] + jnp.stack(drop_blocks)

        # ---- flight recorder + latency histograms (device-resident) ------
        if obs is not None and visits:
            nstages = len(self.stages)
            E = jnp.stack(enters, axis=1)              # (B, nstages)
            X = jnp.stack(exits, axis=1)
            V = jnp.stack(visits, axis=1)              # (B, nstages) bool
            en = (obs["ctrl"]["enable"] != 0)
            en_i = en.astype(jnp.int32)
            # per-stage occupancy (queue depth seen) + end-to-end rows
            occ = X - self._lat_cycles[None, :]
            hrows = [flight.bucket_counts(occ[:, i], V[:, i])
                     for i in range(nstages)]
            e2e = jnp.where(V, X, 0).max(axis=1) - E[:, 0]
            hrows.append(flight.bucket_counts(e2e, V[:, 0]))
            obs["histo"] = obs["histo"] + jnp.stack(hrows) * en_i
            # sampled per-frame trace rows, ONE fused ring append per batch
            fid = obs["frame_ctr"] + jnp.arange(n, dtype=jnp.int32)
            sampled = flight.sample_mask(obs["ctrl"], fid)
            bitmap = jnp.sum(
                jnp.left_shift(V.astype(jnp.int32),
                               jnp.arange(nstages, dtype=jnp.int32)[None, :]),
                axis=1)
            stepcol = jnp.broadcast_to(telem["step"], (n,))
            trow = jnp.concatenate(
                [fid[:, None], stepcol[:, None], bitmap[:, None],
                 first_reason[:, None],
                 jnp.stack([E, X], axis=2).reshape(n, 2 * nstages)], axis=1)
            obs["trace"] = telemetry.append(obs["trace"], trow, sampled)
            obs["frame_ctr"] = obs["frame_ctr"] + n
            telem["obs"] = obs

            # ---- push-mode observability (paper-adjacent INT postcards,
            # series ring, SLO watchdog — repro.obs.{series,postcard,slo})
            if "series" in telem and count_nodes:
                # per-stage TCP retransmission totals (tcp_rx row only):
                # stored cumulatively, so the window delta falls out of
                # the series' cum-prev subtraction like the other metrics
                retx_col = jnp.zeros((nstages,), jnp.int32)
                ccs = state.get("conn")
                ccs = ccs.get("cc") if isinstance(ccs, dict) else None
                if ccs is not None and "tcp_rx" in self._index:
                    total = (ccs["retx_fast"]
                             + ccs["retx_timer"]).sum().astype(jnp.int32)
                    retx_col = retx_col.at[self._index["tcp_rx"]].set(total)
                telem["series"] = series.update(
                    telem["series"], jnp.stack(pkts_in), jnp.stack(drops),
                    jnp.stack(bytes_l), retx_col, obs["histo"])
            if self._mirror_cfg is not None:
                # one fused pack per batch; validity = the recorder's
                # sample mask, so the mirror obeys the same runtime
                # obs_ctrl knobs (TRACE_SET) with no retrace.  lax.cond
                # skips the pack at runtime for batches with no sampled
                # frame (the common case at production 1/64 sampling).
                fb = postcard.frame_bytes(nstages)

                def _pc_pack(_):
                    pc, pl = postcard.pack(
                        self._mirror_cfg, carrier.get("meta"),
                        telem["step"], fid, E, X, V,
                        flight.bucket_of(occ), first_reason)
                    return pc, pl.astype(jnp.int32)

                def _pc_skip(_):
                    return (jnp.zeros((n, fb), jnp.uint8),
                            jnp.zeros((n,), jnp.int32))

                pc, pclen = jax.lax.cond(sampled.any(), _pc_pack,
                                         _pc_skip, None)
                carrier["pc_payload"] = pc
                carrier["pc_len"] = pclen
                carrier["pc_valid"] = sampled
            if self._watchdog_cfg is not None and "slo" in state \
                    and "series" in telem:
                # rules only re-evaluate on the batch that closed a
                # window (wr advanced past the watchdog's last look);
                # edges are rarer still, so the alert pack nests one
                # level deeper
                nr = state["slo"]["active"].shape[0]
                ab = slo.ALERT_BODY_BYTES + postcard.STACK_BYTES
                fresh = telem["series"]["wr"] > state["slo"]["last_wr"]

                def _wd_eval(_):
                    sl, edge, val = slo.evaluate(state["slo"],
                                                 telem["series"])

                    def _al_pack(_):
                        ap, al = slo.alert_frames(
                            self._watchdog_cfg, sl, telem["series"],
                            edge, val)
                        return ap, al.astype(jnp.int32)

                    def _al_skip(_):
                        return (jnp.zeros((nr, ab), jnp.uint8),
                                jnp.zeros((nr,), jnp.int32))

                    ap, al = jax.lax.cond(edge.any(), _al_pack,
                                          _al_skip, None)
                    return sl, edge, ap, al

                def _wd_idle(_):
                    return (state["slo"],
                            jnp.zeros((nr,), jnp.bool_),
                            jnp.zeros((nr, ab), jnp.uint8),
                            jnp.zeros((nr,), jnp.int32))

                sl, edge, ap, al = jax.lax.cond(fresh, _wd_eval,
                                                _wd_idle, None)
                carrier["alert_payload"] = ap
                carrier["alert_len"] = al
                carrier["alert_valid"] = edge
                state["slo"] = sl

        # ---- post-batch table commit (management plane) ------------------
        # A management tile stages table writes in the carrier; they are
        # committed here, after every stage has run, so a command always
        # takes effect on the *next* batch — live reconfiguration with no
        # recompile and no intra-batch ordering hazards (paper §3.6).
        staged = carrier.get("mgmt_staged")
        if staged is not None:
            if staged.get("nat") is not None and "nat" in state:
                state["nat"] = staged["nat"]
            if staged.get("healthy") and "dispatch" in state:
                disp = dict(state["dispatch"])
                for gname, h in staged["healthy"].items():
                    # only the control-owned field: the batch's rr_counter
                    # advances stay intact
                    disp[gname] = dataclasses.replace(disp[gname], healthy=h)
                state["dispatch"] = disp
            if staged.get("routes") is not None:
                state["routes"] = staged["routes"]
            if staged.get("rate") is not None and "rate" in state:
                state["rate"] = staged["rate"]
            if staged.get("cc") is not None and "conn" in state \
                    and "cc" in state["conn"]:
                conn = dict(state["conn"])
                conn["cc"] = staged["cc"]
                state["conn"] = conn
            if staged.get("obs_ctrl") is not None and telem is not None \
                    and "obs" in telem:
                # recorder knobs are runtime state: TRACE_SET takes effect
                # next batch, sampling modulus changes with no retrace
                o = dict(telem["obs"])
                o["ctrl"] = staged["obs_ctrl"]
                telem["obs"] = o
            if staged.get("slo") is not None and "slo" in state:
                # commit rule fields only — the watchdog's own
                # active/last_wr/alerts updates from this batch's
                # evaluation must survive the commit.  A rewritten slot
                # is unlatched (clear_active) so hysteresis restarts
                # from the new thresholds.
                su = staged["slo"]
                s = dict(state["slo"])
                for k in ("metric", "node", "thr_raise", "thr_clear",
                          "enabled"):
                    s[k] = su[k]
                s["active"] = jnp.where(su["clear_active"] != 0,
                                        jnp.zeros_like(s["active"]),
                                        s["active"])
                state["slo"] = s
            if staged.get("series_win") is not None and telem is not None \
                    and "series" in telem:
                ser = dict(telem["series"])
                ser["win_len"] = staged["series_win"]
                telem["series"] = ser
        return state, carrier

    # ---- streaming execution (device-resident multi-batch) ---------------
    def run_stream(self, state: Dict[str, Any], payloads, lengths,
                   out_keys: Optional[Sequence[str]] = None):
        """Run N batches device-resident under ONE ``lax.scan``: state is
        the scan carry, ``payloads`` is a (N, B, L) frame arena with
        (N, B) ``lengths``, and the selected carrier outputs come back
        stacked along the leading axis.  One dispatch, zero host syncs in
        the scanned region, bit-identical to N sequential :meth:`run`
        calls (telemetry counters and post-batch management commits
        included — a table staged by batch i is live for batch i+1
        *inside* the stream).

        Returns ``(state', outs)`` with ``outs[k]`` of shape (N, ...).
        ``out_keys`` selects which carrier keys to stack (default:
        whichever of :data:`STREAM_OUT_KEYS` the chain produces)."""
        keys = self.STREAM_OUT_KEYS if out_keys is None else tuple(out_keys)

        def step(st, xs):
            p, l = xs
            st, carrier = self.run(st, {"payload": p, "length": l})
            return st, {k: carrier[k] for k in keys if k in carrier}

        return jax.lax.scan(step, state, (payloads, lengths))


# ---------------------------------------------------------------------------
# replica-group lowering: RSS dispatch in front of a cloned hot tile
# (core.scaleout.replicate on udp_rx / rs_serve / lm_serve / tcp_rx ...).
# The inner tile fn runs ONCE over the whole batch — replicas are batched
# *lanes*, and the dispatch stage assigns each row its lane from the live
# policy table (flow_hash / round_robin / port_match).  The table is scan-
# carry state, so HEALTH_SET / drain_replica re-balances the lanes on the
# next batch with no retrace, exactly like the app-group dispatch path.


def _replica_group_init(inner: Optional[Callable], gname: str, n: int):
    def init(ctx: TileContext) -> dict:
        from repro.core.scaleout import make_dispatch
        st = inner(ctx) if inner is not None else {}
        deep_merge(st, {"dispatch": {gname: make_dispatch(list(range(n)))}})
        return st
    return init


def _replica_group_fn(inner: Callable, gname: str, policy: str,
                      base_port: Optional[int]):
    def fn(state, carrier, pred, ctx):
        from repro.core.scaleout import dispatch_lane
        # the inner kind may parse the very fields the hash keys on
        # (udp_rx writes src_port/dst_port), so the lane assignment reads
        # the *post-parse* meta — the NIC-RSS view of the full header
        state, carrier, ok = inner(state, carrier, pred, ctx)
        dispatch = dict(state["dispatch"])
        d, lane = dispatch_lane(dispatch[gname], policy, carrier["meta"],
                                pred, base_port)
        dispatch[gname] = d
        state = dict(state)
        state["dispatch"] = dispatch
        carrier = dict(carrier)
        info = dict(carrier["info"])
        info[f"{gname}.lane"] = jnp.where(pred, lane, -1)
        carrier["info"] = info
        return state, carrier, ok
    return fn


# ---------------------------------------------------------------------------
# the generic app-group tile function (dispatch + process, paper §4.2/§5)


def _app_init(ctx: TileContext) -> dict:
    from repro.core.scaleout import make_dispatch
    a = ctx.binding
    if a is None:
        raise CompileError(f"app group {ctx.name!r} has no binding")
    # fresh buffers per init_state: the AppDecl holds its template state
    # by reference, and aliased arrays across two init_state() calls would
    # let a donated run (run_stream's stream_fn) delete another state's
    # buffers out from under it
    fresh = jax.tree_util.tree_map(lambda x: jnp.array(x), a.state)
    return {"dispatch": {a.name: make_dispatch(list(range(a.n_replicas)))},
            "apps": {a.name: fresh}}


@register_tile("app", init=_app_init)
def _app_group(state, carrier, pred, ctx):
    """Replica dispatch + app processing for one app group.

    `pred` IS the arrival predicate derived from the udp_port route
    entries, so port matching lives in the topology, not here."""
    from repro.core.scaleout import dispatch_lane
    a = ctx.binding
    m = carrier["meta"]
    at_app = pred

    dispatch = dict(state["dispatch"])
    apps = dict(state["apps"])
    d, replica = dispatch_lane(dispatch[a.name], a.policy, m, at_app,
                               base_port=a.port)
    dispatch[a.name] = d

    ast, nb, nl = a.process(apps[a.name], carrier["body"], carrier["blen"],
                            m, at_app, replica)
    apps[a.name] = ast
    state = dict(state)
    state["dispatch"] = dispatch
    state["apps"] = apps

    carrier["out_body"] = jnp.where(at_app[:, None], nb, carrier["out_body"])
    carrier["out_blen"] = jnp.where(at_app, nl, carrier["out_blen"])
    info = dict(carrier["info"])
    info[a.name] = at_app
    carrier["info"] = info
    return state, carrier, None
