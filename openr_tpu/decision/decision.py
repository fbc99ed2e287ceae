"""The Decision module: KvStore publications → LSDB → RIB → route deltas.

reference: openr/decision/Decision.cpp † — Decision subscribes to the
KvStore publications queue, parses `adj:<node>` / `prefix:...` keys into
per-area LinkState/PrefixState, debounces bursts with a (min, max)
AsyncThrottle-style window, rebuilds routes, and emits the delta as a
DecisionRouteUpdate on the route-updates queue.

TPU-first divergence: the rebuild is one batched-SSSP kernel launch
(`TpuSpfSolver`) instead of the reference's per-root scalar Dijkstra loop;
the heavy compute runs off the event loop via ``asyncio.to_thread`` so
flooding/RPC latency is never blocked behind a solve.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time
from dataclasses import replace

import numpy as np

from openr_tpu.common import constants as C
from openr_tpu.common.eventbase import OpenrModule
from openr_tpu.common.throttle import AsyncDebounce
from openr_tpu.config import Config
from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.oracle import (
    assemble_prefix_routes as oracle_assemble_prefix_routes,
)
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.decision.oracle import metric_key
from openr_tpu.messaging import QueueClosedError, ReplicateQueue, RQueue
from openr_tpu.monitor import (
    compile_ledger,
    names,
    perf,
    profiling,
    work_ledger,
)
from openr_tpu.monitor import device as device_telemetry
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.routes import (
    RouteDatabase,
    RouteUpdateType,
    diff_route_dbs,
)
from openr_tpu.types.serde import decoder_for, from_wire, to_wire
from openr_tpu.types.topology import (
    Adjacency,
    AdjacencyDatabase,
    PrefixDatabase,
)

log = logging.getLogger(__name__)

_ADJ_DEC = decoder_for(Adjacency)
_ADJDB_DEC = decoder_for(AdjacencyDatabase)
# _adj_reuse bound: an entry holds one node's wire payload (~3 KB at
# degree 32), its raw dicts, the decoded Adjacency tuple + db, and two
# small span arrays — ~25-30 KB total. A tombstone racing a threaded
# decode can strand an entry (no future expiry event), so the cache is
# LRU-capped rather than trusted to drain: 2048 × ~30 KB ≈ 60 MB worst
# case, covering every actively-flapping node of the config-5 bench
_ADJ_REUSE_CAP = 2048

# convergence traces buffered toward the next rebuild: bounded so a
# trace-per-flap storm can't grow the list between debounce fires
# (excess publications still rebuild, just untraced)
_PERF_PENDING_CAP = 64

# empty dirt marker for areas untouched since the last rebuild
_NO_DIRT: frozenset = frozenset()

# consecutive warm-start-free rebuilds before the warm-only artifact
# state (reverse adjacency, pred DAG aux, host distance mirrors) is
# dropped — the soak's memory watermark relies on this staying bounded
# under long structural-churn horizons (docs/Decision.md)
_WARM_IDLE_TRIM = 64

#: Decision.last_breakdown_ms keys older than the span record, and the
#: span each is the wall time of; every other span of a rebuild's record
#: is published under its own name (names.REBUILD_SPANS)
_BREAKDOWN_KEYS = {
    "decode": "decision:decode",
    "apply_snapshot": "decision:apply_snapshot",
    "compute_diff": "decision:compute_diff",
    "compute_rib": "decision:compute_rib",
    "diff": "decision:diff",
}


def _breakdown_view(rec: profiling.SpanRecord) -> dict[str, float]:
    """A rebuild's span record as Decision.last_breakdown_ms: wall ms by
    name, every span of the rebuild path present — 0.0 where the branch
    did not run — so that per-event series of any two names line up."""
    out = dict.fromkeys(names.REBUILD_SPANS, 0.0)
    out.update(rec.ms)
    for key, span in _BREAKDOWN_KEYS.items():
        out[key] = out.pop(span)
    return out


class _TopoDelta:
    """Bounded topology dirt for one area: the directed (node, neighbor)
    pairs whose metric changed (metric-only adjacency updates — the
    classifier downgrades to full topology dirt, ``None``, for anything
    structural), plus whatever prefix dirt rode the same window. The
    rebuild warm-starts the cached SolveArtifact from exactly these
    pairs (REBUILD_TOPO_DELTA) or falls back to a full area solve."""

    __slots__ = ("edges", "prefixes")

    def __init__(self, edges=(), prefixes=()):
        self.edges: set = set(edges)
        self.prefixes: set = set(prefixes)


def _fold_unicast(cur, entry):
    """One cross-area selection step for a unicast prefix: `entry` (from
    a later-sorted area) folded into the current winner `cur`."""
    ek = metric_key(entry.best_entry) if entry.best_entry else (0, 0, 0)
    ck = metric_key(cur.best_entry) if cur.best_entry else (0, 0, 0)
    if ek > ck or (ek == ck and entry.igp_cost < cur.igp_cost):
        return entry
    if ek == ck and entry.igp_cost == cur.igp_cost:
        return replace(
            cur, nexthops=_union_nexthops(cur.nexthops, entry.nexthops)
        )
    return cur


def _fold_mpls(cur, mentry):
    """One cross-area selection step for an MPLS label route: lower IGP
    cost wins outright; equal IGP cost unions the nexthop sets,
    mirroring the unicast equal-cost multi-area ECMP rule (before this,
    the strict `<` compare silently kept only the first sorted area's
    nexthops at a tie)."""
    mi, ci = _mpls_igp(mentry), _mpls_igp(cur)
    if mi < ci:
        return mentry
    if mi > ci or mentry.nexthops == cur.nexthops:
        return cur
    return replace(
        cur, nexthops=_union_nexthops(cur.nexthops, mentry.nexthops)
    )


def merge_area_ribs(
    per_area: dict[str, RouteDatabase], my_node: str
) -> RouteDatabase:
    """Cross-area best-route selection.

    reference: openr/decision/SpfSolver.cpp † selectBestRoutes runs across
    ALL areas' prefix entries: highest metric key wins; at equal metrics and
    equal IGP cost the nexthop sets are unioned (equal-cost multi-area ECMP);
    MPLS label routes follow the same equal-IGP-cost union rule.
    """
    areas = sorted(per_area)
    if len(areas) == 1:
        return per_area[areas[0]]
    out = RouteDatabase(this_node_name=my_node)
    # `merge_full` stage, delta=0: the full fold is the fallback arm of
    # the delta merge book (first build / policy / revision mismatch /
    # solved areas) — honest O(routes) like spf_full, counter-asserted
    # via decision.merge.full, never the steady state. The per-entity
    # Python work below is conflicts-only: non-overlapping entries land
    # through bulk C dict ops, and only prefixes present in BOTH the
    # accumulator and the incoming area run the fold step — same sorted
    # fold order and outcomes as the historical per-prefix loop.
    with work_ledger.scope("merge_full", 0) as ws:
        for area in areas:
            rdb = per_area[area]
            ws.add(len(rdb.unicast_routes) + len(rdb.mpls_routes))
            src_u = rdb.unicast_routes
            dst_u = out.unicast_routes
            if not dst_u:
                out.unicast_routes = dict(src_u)
            else:
                folded = {
                    p: _fold_unicast(dst_u[p], src_u[p])
                    for p in dst_u.keys() & src_u.keys()
                }
                dst_u.update(src_u)
                dst_u.update(folded)
            src_m = rdb.mpls_routes
            dst_m = out.mpls_routes
            if not dst_m:
                out.mpls_routes = dict(src_m)
            else:
                folded_m = {
                    lbl: _fold_mpls(dst_m[lbl], src_m[lbl])
                    for lbl in dst_m.keys() & src_m.keys()
                }
                dst_m.update(src_m)
                dst_m.update(folded_m)
    return out


def merge_scope_delta(
    per_area: dict[str, RouteDatabase],
    base: RouteDatabase,
    scope,
    label_scope=(),
) -> "RouteUpdate":
    """Delta merge book fold: cross-area re-selection for the `scope`
    prefixes (and, for topology-delta rounds, the `label_scope` MPLS
    labels) only, expressed as the RouteUpdate that turns the previous
    merged RIB `base` (the live merge book) into the new merged state.
    Valid because scoped rounds cannot change any out-of-scope route:
    prefix-only rounds touch no MPLS route at all, topology-delta
    rounds report every label whose distance class moved. Folds areas
    in the same sorted order as `merge_area_ribs`, so applying the
    returned update to `base` is byte-equal to a full re-merge.

    The update IS the application delta: an in-scope prefix whose fold
    result equals the book entry (same identity-first compare as
    `diff_route_dbs`) ships nothing; changed entries land in
    `unicast_to_update` / `mpls_to_update`, vanished ones in the delete
    lists. The caller applies it to the book dicts on the event loop —
    O(delta) there, and no O(routes) base-table copy anywhere."""
    from openr_tpu.types.routes import RouteUpdate

    areas = sorted(per_area)
    delta = len(scope) + len(label_scope)
    # touched = one per-area probe per scoped key; ratio ≈ area count
    work_ledger.commit("merge", delta * len(areas), delta)
    uni_up: dict = {}
    uni_del: list = []
    mpls_up: dict = {}
    mpls_del: list = []
    for prefix in scope:
        merged = None
        for a in areas:
            entry = per_area[a].unicast_routes.get(prefix)
            if entry is None:
                continue
            merged = entry if merged is None else _fold_unicast(merged, entry)
        prev = base.unicast_routes.get(prefix)
        if merged is None:
            if prev is not None:
                uni_del.append(prefix)
        elif prev is not merged and prev != merged:
            uni_up[prefix] = merged
    for label in label_scope:
        mmerged = None
        for a in areas:
            mentry = per_area[a].mpls_routes.get(label)
            if mentry is None:
                continue
            mmerged = (
                mentry if mmerged is None else _fold_mpls(mmerged, mentry)
            )
        prev = base.mpls_routes.get(label)
        if mmerged is None:
            if prev is not None:
                mpls_del.append(label)
        elif prev is not mmerged and prev != mmerged:
            mpls_up[label] = mmerged
    return RouteUpdate(
        unicast_to_update=uni_up,
        unicast_to_delete=uni_del,
        mpls_to_update=mpls_up,
        mpls_to_delete=mpls_del,
    )


def _mpls_igp(entry) -> int:
    """IGP cost of an MPLS route = its nexthops' metric (all equal-cost)."""
    return min((nh.metric for nh in entry.nexthops), default=1 << 30)


def _union_nexthops(a, b):
    """Equal-cost multi-area nexthop union. Each side's UCMP weights were
    gcd-normalized independently, so naive set-union could carry duplicate
    (neighbor, interface) slots with clashing weights; dedupe by slot,
    summing weights, and renormalize across the merged set."""
    from openr_tpu.decision.ksp import normalize_weights

    slots: dict[tuple, object] = {}
    wsum: dict[tuple, int] = {}
    weighted = any(nh.weight for nh in (*a, *b))
    for nh in (*a, *b):
        key = (nh.neighbor_node, nh.if_name)
        slots.setdefault(key, nh)
        if weighted:
            wsum[key] = wsum.get(key, 0) + max(nh.weight, 1)
    if weighted:
        wsum = normalize_weights(wsum)
        return tuple(
            sorted(replace(nh, weight=wsum[k]) for k, nh in slots.items())
        )
    return tuple(sorted(slots.values()))


class Decision(OpenrModule):
    """Per-node route computation engine.

    Wiring (reference: Main.cpp †): reads the KvStore publications queue,
    writes the route-updates queue consumed by Fib. Also exposes
    synchronous accessors (`get_route_db`, `get_adj_dbs`, ...) used by the
    OpenrCtrl handler via cross-thread-future-style awaits.
    """

    def __init__(
        self,
        config: Config,
        kvstore_pub_reader: RQueue,
        route_updates_queue: ReplicateQueue,
        solver: str | None = None,  # "tpu" | "cpu" | None (config default)
        counters=None,
        initial_sync_event: "asyncio.Event | None" = None,
    ):
        super().__init__(f"{config.node_name}.decision", counters=counters)
        self.config = config
        self.node_name = config.node_name
        self.pub_reader = kvstore_pub_reader
        self.route_updates = route_updates_queue
        # KVSTORE_SYNCED gate (reference: the initialization process
        # orders KVSTORE_SYNCED before RIB_COMPUTED †): when provided
        # (node.py passes KvStore.initial_sync_done), the FIRST rebuild
        # is deferred until the store finished its initial full sync.
        # Without this a restarted node computes its first RIB from a
        # partial LSDB (typically just its own adj advertisement) and
        # emits a shrunken FULL_SYNC that a warm-booted Fib faithfully
        # programs — wiping every surviving route (chaos-soak finding).
        self._initial_sync_event = initial_sync_event
        self._sync_waiter: "asyncio.Task | None" = None
        self._link_states: dict[str, LinkState] = {
            a: LinkState(a) for a in config.area_ids()
        }
        self._prefix_states: dict[str, PrefixState] = {
            a: PrefixState(a) for a in config.area_ids()
        }
        # raw publication buffer, coalesced by key (last value wins —
        # KvStore delivers versions in increasing order): the hot pub
        # loop only appends; decode + LSDB apply happen once per rebuild
        # via _drain_pending, so 300 coalesced flaps cost ~1 decode per
        # flapping key instead of one per publication, off the per-pub
        # path (config-5 churn measured this as the top host cost)
        self._pending_kvs: dict[tuple[str, str], Value | None] = {}
        # churn decode cache: (area, adj key) → dict(payload, spans,
        # raws, adjs, db) of the last accepted version. A flap re-sends
        # the node's WHOLE AdjacencyDatabase with one metric changed;
        # two reuse tiers avoid re-decoding it:
        #   1. byte-span fast path — the common prefix/suffix against
        #      the cached payload confines the diff to ONE adjacency's
        #      body span, and only those ~100 bytes are parsed (see
        #      _decode_adj_fast for the structural-soundness argument);
        #   2. full parse with raw-dict compare — unchanged Adjacency
        #      objects are reused by C-speed dict equality.
        # Reused identities also make LinkState's old==new /
        # metric-delta comparisons short-circuit. Entries are per-node
        # (LRU-bounded) and dropped on key expiry. Thread-safety:
        # values are replaced, never mutated, and every dict MUTATION
        # (LRU refresh, eviction sweep, expiry pop) holds
        # _adj_reuse_lock — the decode worker thread and the event loop
        # both write here, and GIL-atomicity of single dict ops is not
        # a contract worth betting the LRU sweep's iteration on
        # (r3 advisor finding: the sweep previously caught RuntimeError
        # from mid-iteration resizes instead of excluding them).
        self._adj_reuse: dict[tuple[str, str], dict] = {}
        self._adj_reuse_lock = threading.Lock()
        # observability: byte-splice fast decodes vs full parses vs
        # payload-identical reuses (exported via bench_churn). Updated
        # from both the decode worker thread and the event loop, so
        # increments take the (uncontended) lock — dropped counts would
        # skew the very tier ratios this exists to report
        self.decode_stats = {"fast": 0, "multi": 0, "full": 0, "same": 0}
        self._decode_stats_lock = threading.Lock()
        dcfg = config.node.decision
        backend = solver or ("tpu" if dcfg.use_tpu_solver else "cpu")
        self.backend = backend
        self._tpu = None
        if backend == "tpu":
            # lazy: the cpu/oracle path must not pay the jax import
            from openr_tpu.decision.spf_backend import TpuSpfSolver

            mesh = None
            if dcfg.mesh_sources > 0:
                from openr_tpu.parallel import make_mesh

                mesh = make_mesh(
                    n_sources=dcfg.mesh_sources, n_graph=dcfg.mesh_graph
                )
            self._tpu = TpuSpfSolver(
                enable_lfa=dcfg.enable_lfa,
                ksp_k=dcfg.ksp_paths,
                native_rib=dcfg.native_rib,
                mesh=mesh,
                counters=counters,
            )
        self.debounce = AsyncDebounce(
            dcfg.debounce_min_ms, dcfg.debounce_max_ms, self._rebuild_routes,
            owner=self.name, counters=counters,
        )
        self.rib = RouteDatabase(this_node_name=self.node_name)
        self.rib_computed = asyncio.Event()  # RIB_COMPUTED init gate
        self.rib_policy = None  # set via apply_rib_policy (openr_tpu.policy)
        self._spf_runs = 0
        self._last_spf_ms = 0.0
        # text of the most recent failed rebuild's exception (None until
        # one fails); counted as decision.rebuild.failed
        self.last_rebuild_error: str | None = None
        # where the last successful rebuild spent its time: a view of
        # its span record (_breakdown_view; docs/Monitor.md "Spans")
        self.last_breakdown_ms: dict[str, float] = {}
        # decision:debounce_wait — opened by the first publication
        # buffered since the last rebuild began, closed into the record
        # of the rebuild that picks the batch up (both on the loop
        # thread, in different tasks: not a `with` block)
        self._debounce_span: profiling.Span | None = None
        # perf_counter() at the loop's last line before the solver thread
        # and at the thread's last line (decision:thread_start / _return)
        self._handoff_t: float | None = None
        # perf_counter() of the snapshot behind the most recently
        # EMITTED RouteUpdate, and behind the most recently COMPLETED
        # rebuild (emitted or not) — benchmarks use the pair to attribute
        # a flap to the rebuild that actually contained it, or to prove
        # it produced no route change at all
        self._last_emitted_snapshot_t0 = 0.0
        self._last_completed_snapshot_t0 = 0.0
        # convergence traces of buffered publications (stamped
        # DECISION_RECEIVED; carried into the RouteUpdate the next
        # rebuild emits)
        self._pending_perf: list = []
        # ---- dirty-scoped incremental rebuild state ----------------------
        # area → None (topology dirt: SPF distances may change) | set of
        # IpPrefix touched by prefix-only advertisements since the last
        # rebuild. Accumulated by _drain_pending, consumed by
        # _rebuild_routes AFTER the snapshot (so dirt recorded during
        # the decode await still rides this rebuild). The contract: ALL
        # LSDB mutations flow through process_publication — out-of-band
        # mutations are caught by the LinkState/PrefixState revision
        # checks in _compute_and_diff and fall back to a full rebuild.
        self._dirty: dict[str, set | None] = {}
        # area → PrefixState.rev bumps produced by the drains feeding
        # the next rebuild: the revision check then requires the live
        # rev to equal cached rev + tracked bumps EXACTLY, so an
        # out-of-band prefix mutation is caught even on rounds that
        # also carry legitimate (tracked) prefix dirt
        self._dirty_ps_bumps: dict[str, int] = {}
        # area → LinkState.rev bumps, same contract: with the
        # topology-delta path a TRACKED metric-only adjacency update
        # legitimately advances ls.rev while the cache stays warm, so
        # the guard is cached rev + tracked bumps == live rev exactly —
        # an out-of-band topology mutation still forces a full rebuild
        self._dirty_ls_bumps: dict[str, int] = {}
        # area → {"rdb", "art", "ls_rev", "ps_rev"}: the last rebuild's
        # per-area RouteDatabase + SolveArtifact. Areas with no dirt
        # reuse "rdb" with no solve at all; prefix-only dirt re-assembles
        # just the touched prefixes against "art". Invalidated by
        # topology dirt, revision mismatch, a failed rebuild, or an
        # installed RibPolicy (see docs/Decision.md).
        self._area_cache: dict[str, dict] = {}
        # benchmarking/ops escape hatch: force every rebuild down the
        # from-scratch path (bench_churn --prefix-churn --force-full
        # measures the speedup the scoped pipeline buys with this)
        self.force_full_rebuild = False
        self._area_solves = 0  # _compute_area invocations (SPF solves)
        self._rebuild_path = "full"  # path the last rebuild took
        self._rebuild_cached_areas = 0
        # the last rebuild's dirt held a structural topology change
        # (`_note_dirt`'s None: adjacency set, overload bit, label,
        # weight, expiry), the first RIB's included: every key is new
        self._rebuild_structural = False
        # ---- delta merge book -----------------------------------------
        # self.rib IS the merge book: a persistent merged RIB that
        # scoped rebuilds patch in place with the RouteUpdate produced
        # by merge_scope_delta (thread-side fold, on-loop application).
        # Full-fold rounds (first build / policy / revision mismatch /
        # solved areas) re-arm it wholesale via merge_area_ribs — and
        # the book never aliases a per-area cache rdb (see the detach
        # in _compute_and_diff). "scoped" vs "full" rounds are
        # counter-asserted as decision.merge.scoped / decision.merge.full.
        self._merge_mode = "full"
        # ---- topology-delta warm-start state -------------------------
        # last rebuild's warm-started area count + bounded-region size,
        # and cumulative fallback count (warm attempt that demanded a
        # full solve) — exported as decision.spf.warm_* counters
        self._rebuild_warm_areas = 0
        self._rebuild_warm_region = 0
        self._warm_fallbacks = 0
        # trim policy: consecutive rebuilds that did NOT warm-start;
        # past _WARM_IDLE_TRIM the warm-only artifact state (reverse
        # adjacency, host distance mirrors) is dropped so long soaks
        # with structural churn stay memory-flat (docs/Decision.md)
        self._warm_idle_rounds = 0

    # ------------------------------------------------------------------ run

    async def main(self) -> None:
        self.spawn(self._pub_loop(), name=f"{self.name}.pubs")

    async def cleanup(self) -> None:
        self.debounce.cancel()
        if self._debounce_span is not None:
            self._debounce_span.stop()
            self._debounce_span = None

    # ----------------------------------------------------------- publication

    async def _pub_loop(self) -> None:
        while True:
            try:
                pub = await self.pub_reader.get()
            except QueueClosedError:
                return
            if self.process_publication(pub):
                self.debounce.poke()

    @property
    def link_states(self) -> dict[str, LinkState]:
        """Live LSDB view: draining first keeps every external reader
        (ctrl dumps, validate, tests) consistent with buffered pubs."""
        self._drain_pending()
        return self._link_states

    @property
    def prefix_states(self) -> dict[str, PrefixState]:
        self._drain_pending()
        return self._prefix_states

    def _get_area(self, area: str) -> tuple[LinkState, PrefixState]:
        ls = self._link_states.get(area)
        if ls is None:
            # unknown area: learn it dynamically (reference requires areas
            # pre-configured; we accept them to ease emulation)
            ls = self._link_states[area] = LinkState(area)
            self._prefix_states[area] = PrefixState(area)
        return ls, self._prefix_states[area]

    def process_publication(self, pub: Publication) -> bool:
        """Buffer one publication for the next rebuild; True if it can
        affect routing (reference: Decision::processPublication †, minus
        the eager decode — see _pending_kvs)."""
        area = pub.area
        buffered = False
        for key, val in pub.key_vals.items():
            if val.value is None:
                continue  # ttl refresh — no payload change
            if (
                C.parse_adj_key(key) is not None
                or C.parse_prefix_key(key) is not None
            ):
                self._pending_kvs[(area, key)] = val
                buffered = True
        for key in pub.expired_keys:
            if (
                C.parse_adj_key(key) is not None
                or C.parse_prefix_key(key) is not None
            ):
                self._pending_kvs[(area, key)] = None  # tombstone
                buffered = True
        if buffered and self._debounce_span is None:
            self._debounce_span = profiling.start("decision:debounce_wait")
        if (
            buffered
            and pub.perf_events is not None
            and len(self._pending_perf) < _PERF_PENDING_CAP
        ):
            pub.perf_events.add_perf_event(
                perf.DECISION_RECEIVED, node=self.node_name
            )
            self._pending_perf.append(pub.perf_events)
        return buffered

    def _note_dirt(self, area: str, dirt) -> None:
        """Record rebuild dirt for one applied key. `dirt` is:

          * ``None`` — structural topology dirt (adjacency set /
            overload / label change, adj-key expiry): full solve;
          * a :class:`_TopoDelta` — bounded metric-only edge dirt
            (warm-startable);
          * a set of IpPrefix — prefix-only dirt.

        Structural dirt absorbs everything; edge dirt absorbs prefix
        dirt (the warm round re-assembles the dirty prefixes too)."""
        cur = self._dirty.get(area, _NO_DIRT)
        if dirt is None or cur is None:
            self._dirty[area] = None
        elif isinstance(dirt, _TopoDelta):
            if isinstance(cur, _TopoDelta):
                cur.edges |= dirt.edges
                cur.prefixes |= dirt.prefixes
            elif cur is _NO_DIRT:
                self._dirty[area] = _TopoDelta(dirt.edges, dirt.prefixes)
            else:  # existing prefix-only dirt folds into the delta
                self._dirty[area] = _TopoDelta(
                    dirt.edges, cur | dirt.prefixes
                )
        elif isinstance(cur, _TopoDelta):
            cur.prefixes |= dirt
        elif cur is _NO_DIRT:
            self._dirty[area] = set(dirt)
        else:
            cur |= dirt

    def _drain_pending(self, decoded: dict | None = None) -> bool:
        """Decode + apply the coalesced publication buffer. Idempotent,
        cheap when empty; called from every LSDB reader and at rebuild
        start. `decoded` (from _decode_batch) lets the rebuild path run
        the serde work in the solver thread — only the cheap LSDB apply
        happens on the event loop. Each applied key is classified into
        the per-area dirt set consumed by the next rebuild."""
        if not self._pending_kvs:
            return False
        batch, self._pending_kvs = self._pending_kvs, {}
        changed = False
        # dirt classification is per applied KEY, never per route —
        # one batched add, ratio pinned at 1 by construction
        work_ledger.commit("dirt", len(batch), len(batch))
        for (area, key), val in batch.items():
            ls, ps = self._get_area(area)
            rev0 = ps.rev
            rev0_ls = ls.rev
            if val is None:
                ch, dirt = self._expire_key(ls, ps, key)
            else:
                db = (decoded or {}).get((area, key, id(val)))
                if db is not None:
                    ch, dirt = self._apply_decoded(ls, ps, key, db)
                else:
                    ch, dirt = self._apply_key(ls, ps, key, val)
            bump = ps.rev - rev0
            if bump:
                self._dirty_ps_bumps[area] = (
                    self._dirty_ps_bumps.get(area, 0) + bump
                )
            bump_ls = ls.rev - rev0_ls
            if bump_ls:
                self._dirty_ls_bumps[area] = (
                    self._dirty_ls_bumps.get(area, 0) + bump_ls
                )
            if ch:
                changed = True
                self._note_dirt(area, dirt)
        if changed:
            self.counters and self.counters.increment("decision.lsdb_changes")
        return changed

    @staticmethod
    def _key_schema(key: str):
        """Single source of key-type dispatch shared by the inline and
        threaded decode paths: (expected origin node or None, schema)."""
        node = C.parse_adj_key(key)
        if node is not None:
            return node, AdjacencyDatabase
        parsed = C.parse_prefix_key(key)
        if parsed is not None:
            return parsed[0], PrefixDatabase
        return None, None

    @staticmethod
    def _adj_spans(payload: bytes, adjs: tuple):
        """Byte spans (starts, ends int64 arrays) of each adjacency
        object BODY (interior, without braces) in a canonical
        AdjacencyDatabase payload, or None when untrustworthy.

        The separator scan counts every b'},{' between the array open
        and the last b'}],'. Real inter-object separators are always
        present in the byte stream and fake ones (inside string fields)
        only ADD to the count, so an exact count of n−1 proves the
        middle boundaries are the true ones. The two soft anchors — the
        array head (position-pinned: "adjacencies" sorts first) and the
        rfind'd tail (a trailing string field could contain b'}],') —
        mean a span is only PROVEN once its bytes are checked against
        the parsed adjacency's canonical re-encode; the splice fast
        path does that lazily for the one span it uses, so full parses
        don't pay an O(n) re-encode for reuse that may never happen."""
        n_adjs = len(adjs)
        head = payload.find(b'"adjacencies":[{')
        if head < 0 or n_adjs == 0:
            return None
        start0 = head + 16  # len(b'"adjacencies":[{')
        tail = payload.rfind(b"}],")
        if tail < 0 or tail < start0:
            return None
        seps = []
        p = payload.find(b"},{", start0)
        while p != -1 and p < tail:
            seps.append(p)
            p = payload.find(b"},{", p + 1)
        if len(seps) != n_adjs - 1:
            return None
        starts = np.array([start0] + [s + 3 for s in seps], np.int64)
        ends = np.array([*seps, tail], np.int64)
        return starts, ends

    def _decode_adj_fast(self, payload: bytes, prev: dict):
        """Tier-1 decode: if `payload` differs from the cached previous
        payload only WITHIN one adjacency's body span, parse just that
        body and splice it into the cached objects.

        Soundness: cached spans are re-encode-validated object bodies
        of the previous payload (`_adj_spans`), an invariant this
        method maintains by validating the replacement body the same
        way. The common prefix covers everything before the body and
        the common suffix everything after it, so the new document is
        byte-identical to the old outside the body; the body re-encode
        check proves it is a complete canonical adjacency object
        interior, hence the full parse of the new document would yield
        exactly the spliced result. Anything unproven returns None →
        caller does the full parse.
        """
        pv = prev["payload"]
        if payload == pv:  # TTL refresh / idempotent re-publish
            return prev
        spans = prev["spans"]
        if spans is None:
            return None
        starts, ends = spans
        a = np.frombuffer(payload, np.uint8)
        bb = np.frombuffer(pv, np.uint8)
        m = min(a.size, bb.size)
        neq = a[:m] != bb[:m]
        pre = int(neq.argmax()) if neq.any() else m
        neqr = a[-m:][::-1] != bb[-m:][::-1]
        suf = int(neqr.argmax()) if neqr.any() else m
        suf = min(suf, m - pre)
        delta = a.size - bb.size
        # the only span that can contain the diff start
        i = int(np.searchsorted(starts, pre, side="right")) - 1
        if i < 0:
            return None
        s, e = int(starts[i]), int(ends[i])
        if pre >= e + 3 or suf < bb.size - e:
            return None  # diff in framing, or spills past this body
        proven = prev["proven"]
        if not proven[i]:
            # lazy span proof (see _adj_spans): the OLD bytes of this
            # span must be exactly the canonical encoding of the cached
            # adjacency i, pinning the span to the true object
            # location. Checked at most once per span per generation —
            # the `proven` bitmap carries across splices.
            if to_wire(prev["adjs"][i]) != b"{%s}" % pv[s:e]:
                return None
        body = payload[s : e + delta]
        adj = self._validated_adj_body(body)
        if adj is None:
            return None
        adjs = prev["adjs"][:i] + (adj,) + prev["adjs"][i + 1 :]
        raws = prev["raws"]
        if raws is not None:
            raws = list(raws)
            raws[i] = None  # position decoded without a raw dict
        if delta:
            starts = starts.copy()
            ends = ends.copy()
            starts[i + 1 :] += delta
            ends[i:] += delta
        if not proven[i]:
            proven = proven.copy()
            proven[i] = True
        return {
            "payload": payload,
            "spans": (starts, ends),
            "proven": proven,
            "raws": raws,
            "adjs": adjs,
            "db": replace(prev["db"], adjacencies=adjs),
        }

    @staticmethod
    def _validated_adj_body(body: bytes):
        """Parse one adjacency body and prove it canonical (re-encode
        == input) — the soundness-critical validation shared by BOTH
        splice tiers; returns the Adjacency or None."""
        try:
            # Value PAYLOADS are canonical JSON by contract (docs/
            # Wire.md): the splice proof below re-encodes and compares
            # bytes, which only works against the canonical text form
            adj = _ADJ_DEC(json.loads(b"{%s}" % body))  # orlint: disable=OR011
        except Exception:  # noqa: BLE001 — structural proof failed
            return None
        if to_wire(adj) != b"{%s}" % body:
            return None  # non-canonical body: the span would be unproven
        return adj

    def _decode_adj_multi(self, payload: bytes, prev: dict):
        """Tier-1b decode: MULTIPLE adjacency bodies changed (two flaps
        of the same node coalesced into one debounce window — ~40% of
        churn decodes fell through to the full parse before this tier).

        Re-scans the NEW payload's body spans under the same
        separator-count proof as `_adj_spans`; requires the framing to
        be byte-identical to the cached payload's (the prefix before
        the first body, and the whole suffix from the last body's end —
        which carries every non-adjacency field; the inter-body
        separators are the literal b'},{' by construction of the
        scan). Bodies then pair positionally: byte-equal bodies reuse
        the cached Adjacency objects, differing bodies are parsed and
        canonically re-encode-validated exactly like the single-span
        path (old span proven before the replacement is accepted).
        Anything unproven → None → caller does the full parse."""
        spans_old = prev["spans"]
        if spans_old is None:
            return None
        new_spans = self._adj_spans(payload, prev["adjs"])
        if new_spans is None:
            return None
        starts_o, ends_o = spans_old
        starts_n, ends_n = new_spans
        pv = prev["payload"]
        if payload[: starts_n[0]] != pv[: starts_o[0]]:
            return None
        if payload[ends_n[-1] :] != pv[ends_o[-1] :]:
            return None
        proven = prev["proven"]
        adjs = list(prev["adjs"])
        raws = prev["raws"]
        raws = list(raws) if raws is not None else None
        new_proven = proven.copy()
        changed = 0
        mv_old, mv_new = memoryview(pv), memoryview(payload)
        for i in range(len(adjs)):
            so, eo = int(starts_o[i]), int(ends_o[i])
            sn, en = int(starts_n[i]), int(ends_n[i])
            # zero-copy compare for the unchanged majority; slice to
            # bytes only for the few bodies that get parsed
            if mv_old[so:eo] == mv_new[sn:en]:
                continue
            body = payload[sn:en]
            if not proven[i]:
                # pin the OLD span to the true object location before
                # trusting a positional replacement (see _adj_spans)
                if to_wire(adjs[i]) != b"{%s}" % pv[so:eo]:
                    return None
            adj = self._validated_adj_body(body)
            if adj is None:
                return None
            adjs[i] = adj
            if raws is not None:
                raws[i] = None
            new_proven[i] = True
            changed += 1
        if changed == 0:
            # framing + every body byte-equal ⇒ payload == cached (the
            # caller's identity check handles that first); be safe
            return prev
        adjs_t = tuple(adjs)
        return {
            "payload": payload,
            "spans": new_spans,
            "proven": new_proven,
            "raws": raws,
            "adjs": adjs_t,
            "db": replace(prev["db"], adjacencies=adjs_t),
        }

    def _decode_value(self, area: str, key: str, val: Value, schema):
        """Decode one publication value; AdjacencyDatabase goes through
        the churn reuse cache (see _adj_reuse)."""
        if schema is not AdjacencyDatabase:
            return from_wire(val.value, schema)
        payload = val.value
        if isinstance(payload, str):
            payload = payload.encode()
        cache = self._adj_reuse
        prev = cache.get((area, key))
        entry = None
        tier = "full"
        if prev is not None:
            entry = self._decode_adj_fast(payload, prev)
            if entry is not None:
                tier = "same" if entry is prev else "fast"
            else:
                entry = self._decode_adj_multi(payload, prev)
                if entry is not None:
                    tier = "same" if entry is prev else "multi"
        with self._decode_stats_lock:
            self.decode_stats[tier] += 1
        if entry is None:
            # full-parse tier of the same Value-payload decode cache:
            # payloads are canonical JSON by contract (docs/Wire.md)
            raw = json.loads(payload)  # orlint: disable=OR011
            raws = raw.pop("adjacencies", None) or []
            if prev is not None and prev["raws"] is not None:
                prev_raws, prev_objs = prev["raws"], prev["adjs"]
                n = len(prev_raws)
                adjs = tuple(
                    prev_objs[i]
                    if i < n and prev_raws[i] is not None
                    and r == prev_raws[i]
                    else _ADJ_DEC(r)
                    for i, r in enumerate(raws)
                )
            else:
                adjs = tuple(_ADJ_DEC(r) for r in raws)
            # non-adjacency fields go through the compiled schema
            # decoder — one source of truth, so fields added to
            # AdjacencyDatabase later are never silently dropped here
            db = replace(_ADJDB_DEC(raw), adjacencies=adjs)
            entry = {
                "payload": payload,
                "spans": self._adj_spans(payload, adjs),
                "proven": np.zeros(len(adjs), bool),
                "raws": raws,
                "adjs": adjs,
                "db": db,
            }
        with self._adj_reuse_lock:
            cache.pop((area, key), None)  # refresh LRU position
            cache[(area, key)] = entry
            while len(cache) > _ADJ_REUSE_CAP:
                cache.pop(next(iter(cache)))
        return entry["db"]

    def _decode_batch(self, batch: dict) -> dict:
        """Pure serde decode of a pending-kv batch (thread-safe: touches
        no Decision state beyond the replace-only _adj_reuse cache).
        Keyed by (area, key, id(value)) so a value superseded between
        capture and apply is never misapplied."""
        out = {}
        for (area, key), val in batch.items():
            if val is None:
                continue
            _node, schema = self._key_schema(key)
            if schema is None:
                continue
            try:
                out[(area, key, id(val))] = self._decode_value(
                    area, key, val, schema
                )
            except Exception:  # noqa: BLE001 — fall to _apply_key's path
                continue
        return out

    def _apply_decoded(self, ls, ps, key: str, db):
        """Apply one decoded db; returns (changed, dirt) where dirt is
        None for structural topology changes, a `_TopoDelta` for
        metric-only adjacency updates (the warm-startable class), or
        the set of touched prefixes."""
        if isinstance(db, AdjacencyDatabase):
            node, _schema = self._key_schema(key)
            if node is not None and db.this_node_name != node:
                log.warning(
                    "%s: adj key %s names node %s",
                    self.name, key, db.this_node_name,
                )
            ch, pairs = ls.update_adjacency_db_delta(db)
            if (
                pairs is None
                or not self.config.node.decision.enable_topo_delta
            ):
                return ch, None
            return ch, _TopoDelta(edges=pairs)
        changed = ps.update_prefix_db(db)
        return bool(changed), set(changed)

    def _apply_key(
        self, ls: LinkState, ps: PrefixState, key: str, val: Value
    ):
        _node, schema = self._key_schema(key)
        if schema is None:
            return False, None
        try:
            db = self._decode_value(ls.area, key, val, schema)
        except Exception:  # noqa: BLE001 — corrupt key: ignore
            log.warning("%s: bad db in key %s", self.name, key)
            return False, None
        # update_prefix_db handles delete_prefix tombstones too, keyed
        # consistently by db.this_node_name
        return self._apply_decoded(ls, ps, key, db)

    def _expire_key(self, ls: LinkState, ps: PrefixState, key: str):
        """Returns (changed, dirt) like _apply_decoded: an adj-key
        expiry removes a node from the graph (topology dirt); a prefix
        withdrawal cannot move SPF distances, so it stays prefix dirt."""
        node = C.parse_adj_key(key)
        if node is not None:
            with self._adj_reuse_lock:
                self._adj_reuse.pop((ls.area, key), None)
            return ls.delete_adjacency_db(node), None
        parsed = C.parse_prefix_key(key)
        if parsed is not None:
            pnode, _area, pfx = parsed
            if pfx:
                from openr_tpu.types.network import IpPrefix

                p = IpPrefix(prefix=pfx)
                return ps.withdraw(pnode, p), {p}
            changed = ps.withdraw_node(pnode)
            return bool(changed), set(changed)
        return False, None

    # -------------------------------------------------------------- rebuild

    def _compute_area(
        self, ls: LinkState, ps: PrefixState, want_artifact: bool = False
    ):
        """One area's full solve + assembly. With `want_artifact=True`
        returns (rdb, SolveArtifact | None) for the dirty-scoped cache."""
        self._area_solves += 1
        if self._tpu is not None:
            res = self._tpu.compute_routes(
                ls, ps, self.node_name, return_artifact=want_artifact
            )
        else:
            res = oracle_compute_routes(
                ls, ps, self.node_name,
                enable_lfa=self.config.node.decision.enable_lfa,
                ksp_k=self.config.node.decision.ksp_paths,
                return_artifact=want_artifact,
            )
        # a full solve's "delta" is the solve itself (1): touched is
        # honestly O(area routes) — pre-warm only in steady-state lanes
        rdb = res[0] if want_artifact else res
        work_ledger.commit(
            "spf_full", len(rdb.unicast_routes) + len(rdb.mpls_routes), 1
        )
        return res

    def _reassemble_area(
        self, cache: dict, ps: PrefixState, prefixes: set
    ) -> RouteDatabase:
        """Prefix-only fast path for one area: NO SPF solve or kernel
        launch — route assembly re-runs ONLY for the touched prefixes
        against the cached SolveArtifact; every other unicast route (and
        every MPLS route, which cannot change without topology dirt) is
        reused from the cached per-area RIB verbatim, so the downstream
        diff short-circuits on identity outside the scope."""
        rdb = cache["rdb"]
        art = cache["art"]
        # in-place: the cached per-area RIB is thread-private during a
        # rebuild (the merge book never aliases it — see the detach in
        # _compute_and_diff's full path), so the touched prefixes are
        # patched directly instead of copying the whole table first.
        # touched = the reassembled prefixes only; O(delta) end to end.
        work_ledger.commit("assembly", len(prefixes), len(prefixes))
        if self._tpu is not None:
            entries = self._tpu.assemble_prefix_routes(art, ps, prefixes)
        else:
            entries = oracle_assemble_prefix_routes(art, ps, prefixes)
        for p in prefixes:
            e = entries.get(p)
            if e is None:
                rdb.unicast_routes.pop(p, None)
            else:
                rdb.unicast_routes[p] = e
        return rdb

    def _snapshot_states(self) -> dict[str, tuple[LinkState, PrefixState]]:
        """Taken on the event loop, so the off-thread solve never races
        _pub_loop's LSDB mutations. A PrefixState no publication has
        touched since its last snapshot hands that one out again
        (counted .prefix_shared); else it copies its outer dict
        (.prefix_copied)."""
        link_states = self.link_states  # drains what is pending, once
        prefix_states = self._prefix_states
        shared = sum(ps.snapshot_is_current for ps in prefix_states.values())
        if self.counters:
            # both every time, so that each reads 0 and not nothing
            self.counters.increment("decision.snapshot.prefix_shared", shared)
            self.counters.increment(
                "decision.snapshot.prefix_copied", len(prefix_states) - shared
            )
        return {
            a: (ls.snapshot(), prefix_states[a].snapshot())
            for a, ls in link_states.items()
        }

    def compute_rib(
        self,
        states: dict[str, tuple[LinkState, PrefixState]] | None = None,
    ) -> RouteDatabase:
        """Full cross-area RIB (synchronous; used by rebuild + tests)."""
        if states is None:
            states = self._snapshot_states()
        per_area = {
            a: self._compute_area(ls, ps) for a, (ls, ps) in states.items()
        }
        with profiling.annotate("decision:merge_full"):
            rdb = merge_area_ribs(per_area, self.node_name)
        if self.rib_policy is not None:
            self.rib_policy.apply(rdb)
        return rdb

    def _warm_area(self, ls, ps, cache, d: _TopoDelta):
        """Attempt a topology-delta warm rebuild of one area against its
        cached SolveArtifact; returns (rdb, art, touched_prefixes,
        touched_labels, region) or None to demand a full area solve."""
        max_frac = self.config.node.decision.topo_delta_max_frac
        if self._tpu is not None:
            return self._tpu.warm_compute_routes(
                cache["art"], ls, ps, self.node_name,
                d.edges, d.prefixes, cache["rdb"], max_frac,
            )
        from openr_tpu.decision.oracle import (
            warm_compute_routes as oracle_warm_compute_routes,
        )

        return oracle_warm_compute_routes(
            cache["art"], ls, ps, self.node_name,
            d.edges, d.prefixes, cache["rdb"], max_frac,
        )

    def _compute_and_diff(
        self,
        states,
        dirt: dict | None = None,
        ps_bumps: dict | None = None,
        ls_bumps: dict | None = None,
    ):
        """Thread-side rebuild body: dirty-scoped per-area compute + diff
        against the published RIB (self.rib is only rebound by the
        serialized rebuild coroutine, so reading it here is race-free).

        `dirt` maps area → None (topology dirt) | set of touched
        prefixes, as accumulated by _drain_pending; None for the whole
        argument (legacy callers, e.g. profile_churn_rebuild) means
        every area is topology-dirty — the from-scratch behavior.

        Per-area dispatch:
          * topology dirt, no/invalid cache → full solve (engine SPF),
            cache refreshed with the new RouteDatabase + SolveArtifact;
          * no dirt (revision-verified) → cached RIB reused, ZERO work;
          * prefix-only dirt → scoped reassembly of just the touched
            prefixes against the cached artifact, zero SPF solves.
        When no area needed a solve, the cross-area merge runs as the
        delta book fold (merge_scope_delta): only the touched prefix /
        label scope is re-selected against the live merge book, and the
        resulting RouteUpdate doubles as the diff — no full O(routes)
        merge or sweep anywhere. Fallback-to-full triggers (all of
        which re-arm the book via the full fold): installed RibPolicy,
        force_full_rebuild, first build (empty cache), revision
        mismatch (out-of-band LSDB mutation), artifact absent (node not
        in topology at solve time).
        """
        profiling.stamp("decision:thread_start", self._handoff_t)
        with profiling.annotate("decision:compute_rib"):
            if dirt is None:
                dirt = {a: None for a in states}
            scope: set | None = None
            lscope: tuple | None = None
            cached_areas = 0
            warm_areas = 0
            warm_region = 0
            if self.rib_policy is not None or self.force_full_rebuild:
                # RibPolicy.apply mutates the MERGED rdb in place — which
                # aliases the single-area rdb — so per-area caching is
                # unsound while a policy is installed: recompute from
                # scratch until it is removed/expired (empty cache then
                # forces the next round full, picking up the policy drop)
                self._area_cache.clear()
                new_rib = self.compute_rib(states)
                path = "full"
            else:
                per_area: dict[str, RouteDatabase] = {}
                solved_any = False
                prefix_scope: set = set()
                label_scope_set: set = set()
                bumps = ps_bumps or {}
                lbumps = ls_bumps or {}
                for a, (ls, ps) in states.items():
                    d = dirt.get(a, _NO_DIRT)
                    cache = self._area_cache.get(a)
                    # revision guard: both revs must equal cached rev + the
                    # EXACT bump count the tracked drains produced (the
                    # topology side legitimately advances under tracked
                    # metric-only dirt) — so an out-of-band mutation is
                    # caught even on a round that also carries legitimate
                    # dirt of the same kind
                    if cache is not None and (
                        cache["ls_rev"] + lbumps.get(a, 0) != ls.rev
                        or ps.rev != cache["ps_rev"] + bumps.get(a, 0)
                    ):
                        cache = None  # out-of-band mutation: doubt → full
                    if (
                        isinstance(d, _TopoDelta)
                        and cache is not None
                        and cache["art"] is not None
                    ):
                        res = self._warm_area(ls, ps, cache, d)
                        if res is not None:
                            rdb, art, t_pfx, t_lbl, region = res
                            # warm solve: delta = dirty edges + prefixes,
                            # touched = warm region + reassembled routes
                            work_ledger.commit(
                                "spf_warm",
                                region + len(t_pfx) + len(t_lbl),
                                len(d.edges) + len(d.prefixes),
                            )
                            self._area_cache[a] = {
                                "rdb": rdb, "art": art,
                                "ls_rev": ls.rev, "ps_rev": ps.rev,
                            }
                            prefix_scope |= t_pfx
                            label_scope_set |= t_lbl
                            warm_areas += 1
                            warm_region += region
                            per_area[a] = rdb
                            continue
                        self._warm_fallbacks += 1
                        d = None  # warm refused: full solve for this area
                    elif isinstance(d, _TopoDelta):
                        d = None  # no warmable cache: full solve
                    # the artifact is only needed for prefix-dirt
                    # reassembly: a no-dirt area reuses its cached rdb even
                    # when the artifact is None (node outside the topology
                    # at solve time — the cached rdb is correctly empty)
                    if d is None or cache is None or (
                        d and cache["art"] is None
                    ):
                        rdb, art = self._compute_area(
                            ls, ps, want_artifact=True
                        )
                        self._area_cache[a] = {
                            "rdb": rdb, "art": art,
                            "ls_rev": ls.rev, "ps_rev": ps.rev,
                        }
                        solved_any = True
                    elif not d:
                        rdb = cache["rdb"]
                        cached_areas += 1
                    else:
                        rdb = self._reassemble_area(cache, ps, d)
                        cache["rdb"] = rdb
                        cache["ps_rev"] = ps.rev
                        prefix_scope |= d
                    per_area[a] = rdb
                if solved_any:
                    path = "full"
                    with profiling.annotate("decision:merge_full"):
                        new_rib = merge_area_ribs(per_area, self.node_name)
                        if len(per_area) == 1:
                            # detach the merge book from the per-area
                            # cache: the single-area fast path returns the
                            # cached rdb itself, and the book must never
                            # alias it (scoped rounds patch cache rdbs in
                            # place off-loop, while ctrl readers hold
                            # self.rib on the event loop). Bulk C dict
                            # copy, full-rebuild rounds only.
                            detached = RouteDatabase(
                                this_node_name=self.node_name
                            )
                            detached.unicast_routes = dict(
                                new_rib.unicast_routes
                            )
                            detached.mpls_routes = dict(new_rib.mpls_routes)
                            new_rib = detached
                else:
                    path = "topo_delta" if warm_areas else "prefix_only"
                    scope = prefix_scope
                    lscope = tuple(sorted(label_scope_set))
                    # delta merge book: fold ONLY the scoped keys across
                    # the per-area RIBs and express the result as the
                    # RouteUpdate that patches the live book. self.rib is
                    # read-only in this worker thread; _rebuild_routes
                    # applies the update in place on the event loop. No
                    # base-table copy — the round is O(delta × areas).
                    with profiling.annotate("decision:merge_scope"):
                        update = merge_scope_delta(
                            per_area, self.rib, scope, lscope
                        )
                    new_rib = self.rib
        with profiling.annotate("decision:diff"):
            self._merge_mode = "scoped" if scope is not None else "full"
            if scope is not None:
                # the book fold above already produced the exact delta with
                # diff semantics (identity-first compare); the diff stage
                # records the scoped comparisons it performed — ratio 1
                work_ledger.commit(
                    "diff",
                    len(scope) + len(lscope),
                    len(scope) + len(lscope),
                )
            else:
                # full sweep walks both tables; no delta to credit
                work_ledger.commit(
                    "diff",
                    len(self.rib.unicast_routes)
                    + len(self.rib.mpls_routes)
                    + len(new_rib.unicast_routes)
                    + len(new_rib.mpls_routes),
                    0,
                )
                update = diff_route_dbs(self.rib, new_rib)
            self._rebuild_path = path
            self._rebuild_cached_areas = cached_areas
            self._rebuild_warm_areas = warm_areas
            self._rebuild_warm_region = warm_region
        self._handoff_t = time.perf_counter()
        return new_rib, update

    async def _rebuild_routes(self) -> None:
        if (
            self._initial_sync_event is not None
            and not self._initial_sync_event.is_set()
            and not self.rib_computed.is_set()
        ):
            # hold the first RIB until KVSTORE_SYNCED; a waiter re-pokes
            # the debounce the moment the gate opens so the deferred
            # batch still rebuilds promptly
            if self._sync_waiter is None or self._sync_waiter.done():
                self._sync_waiter = self.spawn(
                    self._poke_after_initial_sync(),
                    name=f"{self.name}.syncgate",
                )
            return
        # one span record per rebuild (docs/Monitor.md "Spans"): every
        # host phase from the batch's first publication to the push
        # closes into it, the solver thread's too (the record follows
        # asyncio.to_thread); last_breakdown_ms is its published view
        with profiling.collect() as rec:
            held, self._debounce_span = self._debounce_span, None
            if held is not None:
                held.stop(rec)
            with profiling.annotate("decision:rebuild"):
                done = await self._rebuild(rec)
            if done:
                # published breakdown (round-2 verdict item 3): where a
                # steady-state churn rebuild actually spends its time
                self.last_breakdown_ms = _breakdown_view(rec)
                if self._tpu is not None and self._rebuild_path == "full":
                    await self._prewarm_flap_programs(rec)

    async def _prewarm_flap_programs(self, rec: profiling.SpanRecord) -> None:
        """After a rebuild that solved an area in full: have every
        program the next metric-only event can need compiled before that
        event's rebuild begins (TpuSpfSolver.prewarm_flap_programs,
        docs/Decision.md "Pre-warmed flap programs"). The routes are out
        already and Fib programs them meanwhile; this coroutine is the
        debounce's, so no rebuild starts until it returns. In the worker
        thread, as the solve was: a compile must not hold the loop."""
        arts = [
            c["art"] for c in self._area_cache.values()
            if c["art"] is not None
        ]
        if arts and await asyncio.to_thread(
            lambda: sum(map(self._tpu.prewarm_flap_programs, arts))
        ):
            self.last_breakdown_ms = _breakdown_view(rec)
            if self.counters:
                self.counters.set(
                    "decision.spf.prewarm_programs",
                    self._tpu.spf_kernel_stats["prewarm_programs"],
                )

    async def _rebuild(self, rec: profiling.SpanRecord) -> bool:
        """One rebuild under the open record `rec`; False when it failed
        (the old RIB keeps serving)."""
        traces: list = []
        try:
            # serde decode of the coalesced flap backlog runs in the
            # worker thread (pure; keyed by value identity so a key
            # superseded mid-flight falls back to inline decode); the
            # event loop only pays the cheap LSDB apply + snapshot, so
            # publication processing never stalls behind a rebuild
            decoded = None
            if self._pending_kvs:
                batch_view = dict(self._pending_kvs)
                with profiling.annotate("decision:decode"):
                    decoded = await asyncio.to_thread(
                        self._decode_batch, batch_view
                    )
            with profiling.annotate("decision:apply_snapshot"):
                if decoded is not None:
                    self._drain_pending(decoded)
                # take the traces AFTER the decode await:
                # _snapshot_states' drain folds in publications that
                # arrived during it, so their route changes ship in
                # THIS update — their traces must ride along, not wait
                # for a (typically empty) next rebuild. Anything
                # arriving after the snapshot stays pending for the
                # rebuild that will actually contain it.
                traces, self._pending_perf = self._pending_perf, []
                for pe in traces:
                    pe.add_perf_event(
                        perf.DECISION_DEBOUNCED, node=self.node_name
                    )
                with profiling.annotate("decision:snapshot"):
                    states = self._snapshot_states()
                # consume the dirt AFTER the snapshot: everything the
                # snapshot folded in has its dirt recorded by now, and
                # anything arriving later stays pending for the rebuild
                # that will actually contain it
                dirt, self._dirty = self._dirty, {}
                self._rebuild_structural = None in dirt.values()
                ps_bumps, self._dirty_ps_bumps = self._dirty_ps_bumps, {}
                ls_bumps, self._dirty_ls_bumps = self._dirty_ls_bumps, {}
            with profiling.annotate("decision:compute_diff"):
                # the two hand-offs, loop → solver thread → loop, begin in
                # one thread and end in the other, where no TraceAnnotation
                # can go: spans of the record alone (profiling.stamp), the
                # clock read passed through _handoff_t (one rebuild runs
                # at a time)
                self._handoff_t = time.perf_counter()
                new_rib, update = await asyncio.to_thread(
                    self._compute_and_diff, states, dirt, ps_bumps, ls_bumps
                )
                profiling.stamp("decision:thread_return", self._handoff_t)
                self._handoff_t = None
        except asyncio.CancelledError:
            raise  # node shutdown mid-rebuild must propagate (OR005)
        except Exception as exc:  # noqa: BLE001 — keep serving the old RIB
            log.exception("%s: route rebuild failed", self.name)
            # a solver that cannot compile or run (first contact with a
            # new chip, a bad kernel) would otherwise show up only as a
            # RIB that never arrives: count it and keep the text where
            # ctrl, the emulator and chip_smoke.py can read it
            self.last_rebuild_error = f"{type(exc).__name__}: {exc}"
            if self.counters:
                self.counters.increment("decision.rebuild.failed")
                self.counters.flight_record(
                    "decision.rebuild_failed",
                    error=self.last_rebuild_error[:200],
                )
            # the dirt describing this batch was consumed but its routes
            # never landed: drop the per-area caches so the next rebuild
            # is a from-scratch one instead of trusting a stale artifact.
            # The merge book (self.rib) is still consistent with the
            # published routes — scoped updates are only applied after a
            # successful thread return — and the forced full round
            # re-arms it wholesale.
            self._area_cache.clear()
            # re-queue the already-dequeued traces so the retrying
            # rebuild (which WILL contain these publications' route
            # changes) completes them — otherwise the slowest, failure-
            # retried convergence events would vanish from the very
            # metric this tracing exists to surface. `traces` was POPPED
            # from _pending_perf before the awaits and the RHS re-reads
            # the CURRENT list, so this fold loses nothing — not a
            # stale-read clobber:
            self._pending_perf = (  # orlint: disable=OR003
                traces + self._pending_perf
            )[:_PERF_PENDING_CAP]
            return False
        with profiling.annotate("decision:export_counters"):
            self._export_rebuild(rec, traces)
        with profiling.annotate("decision:publish"):
            self._publish(rec.t0, new_rib, update, traces)
        return True

    def _export_rebuild(self, rec: profiling.SpanRecord, traces) -> None:
        """Everything a finished rebuild stamps and exports before its
        routes go out: markers, the trim policy, the counter surface."""
        self._last_spf_ms = rec.elapsed_ms()
        self._spf_runs += 1
        path = self._rebuild_path
        marker = {
            "prefix_only": perf.REBUILD_PREFIX_ONLY,
            "topo_delta": perf.REBUILD_TOPO_DELTA,
        }.get(path, perf.REBUILD_FULL)
        for pe in traces:
            pe.add_perf_event(marker, node=self.node_name)
            pe.add_perf_event(perf.SPF_SOLVE_DONE, node=self.node_name)
        # warm-state trim policy: after _WARM_IDLE_TRIM consecutive
        # rebuilds with no warm start, drop the warm-only artifact state
        # (rebuilt/re-fetched on demand) so purely-structural or
        # prefix-only churn never pins warm memory indefinitely
        if self._rebuild_warm_areas:
            self._warm_idle_rounds = 0
        else:
            self._warm_idle_rounds += 1
            if self._warm_idle_rounds == _WARM_IDLE_TRIM:
                self.trim_warm_state()
        if self.counters:
            self.counters.flight_record(
                "decision.rebuild",
                path=path or "full",
                ms=round(self._last_spf_ms, 3),
                traces=len(traces),
            )
            self.counters.increment("decision.spf_runs")
            if path == "prefix_only":
                self.counters.increment("decision.rebuild.prefix_only")
            elif path == "topo_delta":
                self.counters.increment("decision.rebuild.topo_delta")
            else:
                self.counters.increment("decision.rebuild.full")
            if self._rebuild_structural:
                self.counters.increment("decision.rebuild.structural")
            if self._rebuild_cached_areas:
                self.counters.increment(
                    "decision.rebuild.cached_areas",
                    self._rebuild_cached_areas,
                )
            # merge-book path counters: the fallback-matrix assertion
            # surface (docs/Decision.md) — steady state increments only
            # .scoped; any .full increment names a fallback round
            if self._merge_mode == "scoped":
                self.counters.increment("decision.merge.scoped")
            else:
                self.counters.increment("decision.merge.full")
            if self._rebuild_warm_areas:
                self.counters.increment(
                    "decision.spf.warm_starts", self._rebuild_warm_areas
                )
                self.counters.add_value(
                    "decision.spf.warm_region_nodes",
                    self._rebuild_warm_region,
                )
            self.counters.set(
                "decision.spf.warm_fallbacks", self._warm_fallbacks
            )
            self.counters.set(
                "decision.rebuild.area_solves", self._area_solves
            )
            # counted in _publish; touched here so that it reads 0, not
            # nothing, until the first rebuild that changes no route
            self.counters.increment("decision.rebuild.no_change", 0)
            self.counters.set("decision.spf_ms", self._last_spf_ms)
            # windowed latency stats (exported as .p50/.p99 per window):
            # the solve+assembly+diff core, and the full rebuild
            self.counters.add_value(
                "decision.spf_solve_ms", rec.ms["decision:compute_rib"]
            )
            self.counters.add_value("decision.rebuild_ms", self._last_spf_ms)
            # steady-state work ledger (monitor/work_ledger.py): per-
            # stage touched/delta/ratio gauges. Host accounting — NOT
            # TPU-branch-gated like the compile/device ledgers: every
            # engine walks the same dataflow stages
            work_ledger.export_to(self.counters)
            # the garbage collector's process totals (runtime.gc.*): what
            # of a convergence tail is the collector's; its pauses inside
            # this rebuild are the record's spf:gc / decision:gc spans
            profiling.export_gc_to(self.counters)
            with self._decode_stats_lock:
                for tier, n in self.decode_stats.items():
                    self.counters.set(f"decision.decode.{tier}", n)
            if self._tpu is not None:
                for k, n in self._tpu.dev_cache_stats.items():
                    self.counters.set(f"decision.dev_cache.{k}", n)
                for k, n in self._tpu.spf_kernel_stats.items():
                    self.counters.set(f"decision.spf.{k}", n)
                for k, n in self._tpu.elect_stats.items():
                    self.counters.set(f"decision.elect.{k}", n)
                phase_ms = self._tpu.last_phase_ms
                for k in ("election", "assembly", "mpls"):
                    if k in phase_ms:
                        stat = f"{k}_ms"
                        self.counters.add_value(
                            f"decision.elect.{stat}", phase_ms[k]
                        )
                self.counters.set(
                    "decision.nexthop_groups", len(self._tpu._nh_intern)
                )
                self.counters.set(
                    "decision.spf.solves", self._tpu.solve_count
                )
                # process-wide jax compile/transfer ledger (zeroes
                # until monitor.compile_ledger.install() hooks
                # jax_log_compiles — tests/conftest and the bench/churn
                # lanes install it; see docs/Monitor.md). Must stay in
                # the TPU branch — the engine that actually jits
                # (review finding: the oracle else-branch briefly
                # captured it, flatlining the metrics where compiles
                # can occur)
                compile_ledger.export_to(self.counters)
                # device telemetry plane (monitor/device.py): kernel
                # cost rows captured at trace time + per-device HBM
                # gauges sampled at this rebuild edge. Same TPU-branch
                # rule as the compile ledger — only the jitting engine
                # has device executables to account
                device_telemetry.export_to(self.counters)
                device_telemetry.sample_hbm(self.counters)
            else:
                self.counters.set(
                    "decision.nexthop_groups",
                    sum(
                        len(c["art"].nh_intern)
                        for c in self._area_cache.values()
                        if c.get("art") is not None
                        and c["art"].nh_intern is not None
                    ),
                )

    def _publish(self, t0: float, new_rib, update, traces) -> None:
        """Land the rebuild's routes in the merge book and push them. On
        the event loop with no await: ctrl readers never see a torn
        table. `t0`: perf_counter() when the rebuild began."""
        first = not self.rib_computed.is_set()
        if new_rib is self.rib:
            # delta merge book: apply the scoped update to the live
            # book in place — on the event loop with no awaits between
            # here and the push, so ctrl readers never observe a torn
            # table and downstream consumers see exactly the update we
            # ship. O(delta) application; bulk C dict ops.
            rib = self.rib
            rib.unicast_routes.update(update.unicast_to_update)
            for p in update.unicast_to_delete:
                rib.unicast_routes.pop(p, None)
            rib.mpls_routes.update(update.mpls_to_update)
            for lbl in update.mpls_to_delete:
                rib.mpls_routes.pop(lbl, None)
        else:
            self.rib = new_rib
        self._last_completed_snapshot_t0 = t0
        if first or not update.empty():
            self._last_emitted_snapshot_t0 = t0
            for pe in traces:
                pe.add_perf_event(
                    perf.ROUTE_UPDATE_SENT, node=self.node_name
                )
            update.perf_events = traces
        elif self.counters:
            # the rebuild proved no route change — the traces end here
            self.counters.increment("decision.rebuild.no_change")
        if first:
            update.type = RouteUpdateType.FULL_SYNC
            self.rib_computed.set()
            self.route_updates.push(update)
        elif not update.empty():
            self.route_updates.push(update)

    async def _poke_after_initial_sync(self) -> None:
        await self._initial_sync_event.wait()
        self.debounce.poke()

    # ------------------------------------------------------------ accessors

    def warm_cache_bytes(self) -> int:
        """Rough footprint of the warm-start-only solve state across
        every cached area artifact (what `trim_warm_state` reclaims) —
        the soak memory watermark samples this per node."""
        total = 0
        for cache in self._area_cache.values():
            art = cache.get("art")
            if art is not None:
                total += art.warm_state_bytes()
        return total

    def prefix_table_bytes(self) -> int:
        """Rough footprint of the prefix table (PrefixState entry maps)
        plus the nexthop-group intern tables — the soak memory
        watermark samples this per node per round, so a churn horizon
        that leaks withdrawn prefixes or grows the intern table without
        bound trips the invariant instead of hiding inside total RSS."""
        import sys

        total = 0
        for ps in self._prefix_states.values():
            total += sys.getsizeof(ps.prefixes)
            for per in ps.prefixes.values():  # orlint: disable=OR012,OR013 — soak sampler, once per round, never on a rebuild/program path; not a ledger stage
                # per-advertiser dict + a rough constant per frozen
                # PrefixEntry (slots=True: no instance dict)
                total += sys.getsizeof(per) + 96 * len(per)
        if self._tpu is not None:
            total += 120 * len(self._tpu._nh_intern)
        for c in self._area_cache.values():
            art = c.get("art")
            if art is not None and getattr(art, "nh_intern", None) is not None:
                total += 120 * len(art.nh_intern)
        return total

    def trim_warm_state(self) -> None:
        """Drop warm-start-only memory (reverse adjacency, host
        distance-matrix mirrors) from every cached artifact, keeping
        the prefix-only fast path intact; the next topology-delta round
        rebuilds what it needs or falls back to one full solve."""
        for cache in self._area_cache.values():
            art = cache.get("art")
            if art is not None:
                art.drop_warm_state()
        if self._tpu is not None:
            self._tpu.trim_caches()

    def set_rib_policy(self, policy) -> None:
        """Install/replace the RibPolicy and recompute (reference:
        OpenrCtrl setRibPolicy → Decision †). A recompute is also
        scheduled at the policy's TTL expiry so stale weights don't
        outlive it on a quiet network."""
        self.rib_policy = policy
        self.debounce.poke()
        if policy is not None and getattr(policy, "ttl_secs", None):
            self.spawn(
                self._policy_expiry_watch(policy),
                name=f"{self.name}.policy-ttl",
            )

    async def _policy_expiry_watch(self, policy) -> None:
        await asyncio.sleep(policy.ttl_secs)
        if self.rib_policy is policy:
            self.rib_policy = None  # expired: drop and recompute unweighted
            self.debounce.poke()

    def get_rib_policy(self):
        return self.rib_policy

    def get_route_db(self) -> RouteDatabase:
        return self.rib

    def get_spf_path(
        self, src: str, dst: str, area: str | None = None
    ) -> dict:
        """Deterministic shortest path src→dst from the current LSDB
        (reference: breeze `decision path` † — upstream answers the
        same operator question with a host-side query). One path query
        is host work: same adjacency build, overload semantics, and
        smallest-name tie-break rule as the oracle/KSP backends, so
        the answer is byte-consistent with the computed RIB.
        """
        from openr_tpu.decision.ksp import dijkstra, extract_path
        from openr_tpu.decision.oracle import build_adjacency

        from openr_tpu.common.constants import DIST_INF

        areas = (
            [area] if area is not None else sorted(self._link_states)
        )
        # border nodes can sit in several areas: answer with the best
        # reachable path across every candidate area, not whatever the
        # first sorted area says (review finding)
        best: dict | None = None
        for a in areas:
            ls = self._link_states.get(a)
            if ls is None or src not in ls.nodes or dst not in ls.nodes:
                continue
            if src == dst:
                return {
                    "area": a, "src": src, "dst": dst,
                    "reachable": True, "cost": 0, "hops": [src],
                    "hop_metrics": [],
                }
            adj = build_adjacency(ls)
            overloaded = {
                n for n in ls.nodes if ls.is_node_overloaded(n)
            }
            dist = dijkstra(adj, src, overloaded)
            # same DIST_INF saturation cutoff as oracle.run_spf and the
            # device kernels: a cost at or past the sentinel is
            # unreachable in the computed RIB (review finding)
            if dist.get(dst, DIST_INF) >= DIST_INF:
                continue
            hops = extract_path(adj, dist, src, dst, overloaded)
            if hops is None:
                continue
            if best is None or int(dist[dst]) < best["cost"]:
                # extract_path returns root→dest order
                best = {
                    "area": a, "src": src, "dst": dst,
                    "reachable": True, "cost": int(dist[dst]),
                    "hops": hops,
                    "hop_metrics": [
                        int(adj[u][v]) for u, v in zip(hops, hops[1:])
                    ],
                }
        return best or {"src": src, "dst": dst, "reachable": False}

    def get_adj_dbs(self) -> dict[str, list[AdjacencyDatabase]]:
        return {
            area: [db for n in ls.nodes if (db := ls.adjacency_db(n))]
            for area, ls in self.link_states.items()
        }

    def get_received_routes(self) -> dict[str, dict]:
        return {
            area: {  # orlint: disable=OR012,OR013 — operator accessor (breeze received-routes dump), not a rebuild path or ledger stage
                str(p.prefix): sorted(per_node)
                for p, per_node in ps.prefixes.items()
            }
            for area, ps in self.prefix_states.items()
        }
