"""LSDB state: the graph (LinkState) and advertised prefixes (PrefixState).

reference: openr/decision/LinkState.{h,cpp} † (adjacency graph, bidirectional
adjacency check, overload semantics, SPF memoization) and
openr/decision/PrefixState.{h,cpp} † (prefix → advertising nodes map).

TPU-first design: `LinkState` maintains the host-side authoritative graph
keyed by names, and lazily materializes a **padded CSR edge list**
(`CsrGraph`) — fixed, bucketed array shapes so the jitted SPF kernel never
recompiles as the topology churns. Node and edge capacities grow by
power-of-two buckets; invalid slots are masked with `INF_METRIC`.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field, replace

import numpy as np

from openr_tpu.common.constants import DEFAULT_AREA, DIST_INF, METRIC_MAX
from openr_tpu.common.util import pad_bucket  # noqa: F401  (re-export)
from openr_tpu.types.topology import (
    Adjacency,
    AdjacencyDatabase,
    PrefixDatabase,
    PrefixEntry,
)
from openr_tpu.types.network import IpPrefix

# Metric sentinel for masked/invalid edge slots. Valid metrics are clamped
# to METRIC_MAX so the int32 relax step in ops/spf.py cannot overflow.
INF_METRIC = DIST_INF

# process-wide monotonic CsrGraph version counter (anchors patch journals)
_csr_version = itertools.count(1)
_PS_LINEAGE = itertools.count(1)


@dataclass(frozen=True)
class MetricPatch:
    """One metric-only edge update in a CsrGraph patch journal.

    reference analogue: the reference's LinkState SPF-cache invalidation
    distinguishes LINK_ATTRIBUTES changes from topology changes †; this is
    the rebuild's sharper version — a metric-only change is *data*, so it
    patches the padded arrays (host and device) instead of rebuilding
    them. `edge_idx` is the slot in the edge-list arrays, (dense_row,
    dense_col) the slot in the dense in-neighbor tables.
    """

    edge_idx: int
    dense_row: int
    dense_col: int
    metric: int


@dataclass
class CsrGraph:
    """Padded, device-ready edge-list view of the LSDB.

    Edge arrays are sorted by destination node so that `segment_min` over
    `edge_dst` (the relax step's scatter-min) is a contiguous segmented
    reduction — the layout XLA lowers best on TPU.

    Arrays (shapes fixed by buckets):
      edge_src[Ep]      i32  source node id (0 for padding)
      edge_dst[Ep]      i32  destination node id (num_nodes_padded-1 slot ok;
                             padding edges point at a dead slot with INF metric)
      edge_metric[Ep]   i32  directed metric ≤ METRIC_MAX; INF_METRIC padding
      node_overloaded[Vp] bool  node overload (no-transit) bits
      node_mask[Vp]     bool  which node slots are live
    """

    num_nodes: int
    num_edges: int
    edge_src: np.ndarray
    edge_dst: np.ndarray
    edge_metric: np.ndarray
    node_overloaded: np.ndarray
    node_mask: np.ndarray
    node_names: list[str]
    # host-side maps for building NextHops from solver output:
    # (src_id, dst_id) -> list[(if_name, metric, weight, adj_label, other_if)]
    adj_details: dict[tuple[int, int], list[tuple[str, int, int, int, str]]]
    name_to_id: dict[str, int]
    # metric-patched entries overriding adj_details (shared base stays
    # untouched; the override dict holds only churned edges, so a 50-flap
    # rebuild copies ~50 entries instead of the whole O(E) dict). Read
    # through `details()` / `details_get()`.
    adj_overrides: dict[tuple[int, int], list] = field(default_factory=dict)
    _dense: tuple[np.ndarray, np.ndarray] | None = None
    _dense_width: int | None = None
    _row_start: np.ndarray | None = None
    # --- incremental-churn support ------------------------------------
    # (src_id, dst_id) -> edge-array slot (built once per base)
    edge_index: dict[tuple[int, int], int] = field(default_factory=dict)
    # unique id of this materialization; patched copies keep the base's
    # id in `base_version` plus the cumulative journal that produced them,
    # so the TPU backend can scatter-update device-resident arrays
    version: int = 0
    base_version: int = 0
    patches: tuple["MetricPatch", ...] = ()

    def details(self, u: int, v: int):
        """Adjacency details for edge (u, v), override-aware."""
        got = self.adj_overrides.get((u, v))
        return got if got is not None else self.adj_details[(u, v)]

    def details_get(self, u: int, v: int, default=None):
        got = self.adj_overrides.get((u, v))
        if got is not None:
            return got
        return self.adj_details.get((u, v), default)

    @property
    def padded_nodes(self) -> int:
        return len(self.node_mask)

    @property
    def padded_edges(self) -> int:
        return len(self.edge_src)

    def dense_width(self) -> int:
        """D of the dense tables WITHOUT building them (cached O(E)
        bincount) — used to decide dense-vs-edge-list before committing
        the memory. Safe to cache: CsrGraph is immutable (LinkState drops
        the whole object on any topology change)."""
        if self._dense_width is None:
            valid = self.edge_metric < DIST_INF
            if not valid.any():
                self._dense_width = 8
            else:
                indeg = np.bincount(
                    self.edge_dst[valid].astype(np.int64),
                    minlength=self.padded_nodes,
                )
                self._dense_width = pad_bucket(int(indeg.max()), minimum=8)
        return self._dense_width

    def dense_tables(self) -> tuple[np.ndarray, np.ndarray]:
        """Cached dense in-neighbor tables (see ops.spf.build_dense_tables)."""
        if self._dense is None:
            from openr_tpu.ops.spf import build_dense_tables

            self._dense = build_dense_tables(
                self.edge_src, self.edge_dst, self.edge_metric,
                self.padded_nodes,
            )
        return self._dense

    def row_start(self) -> np.ndarray:
        """First dst-sorted edge index per destination node (cached —
        CsrGraph is immutable). O(E) once instead of a searchsorted per
        dense_col call on the churn path."""
        if self._row_start is None:
            counts = np.bincount(
                self.edge_dst[: self.num_edges].astype(np.int64),
                minlength=self.padded_nodes,
            )
            self._row_start = np.concatenate(
                ([0], np.cumsum(counts))
            ).astype(np.int64)
        return self._row_start

    def dense_col(self, edge_idx: int, dst: int) -> int:
        """Dense-table column of edge slot `edge_idx` (the dense layout
        follows the dst-sorted edge order, so the column is the rank of
        the edge within its destination's run)."""
        return edge_idx - int(self.row_start()[dst])


def _metric_only_delta(
    old: AdjacencyDatabase, new: AdjacencyDatabase
) -> list[Adjacency] | None:
    """The adjacencies whose metric (or rtt) changed, or None if anything
    *structural* differs (adjacency set, overload bits, labels, weights —
    those need a full CSR rebuild)."""
    if (
        old.this_node_name != new.this_node_name
        or old.is_overloaded != new.is_overloaded
        or old.node_label != new.node_label
        or len(old.adjacencies) != len(new.adjacencies)
    ):
        return None
    delta: list[Adjacency] = []
    for oa, na in zip(old.adjacencies, new.adjacencies):
        if oa is na:  # Decision's decode cache reuses unchanged objects
            continue
        if (
            oa.other_node_name != na.other_node_name
            or oa.if_name != na.if_name
            or oa.other_if_name != na.other_if_name
            or oa.adj_label != na.adj_label
            or oa.is_overloaded != na.is_overloaded
            or oa.weight != na.weight
        ):
            return None
        if oa.metric != na.metric or oa.rtt_us != na.rtt_us:
            delta.append(na)
    return delta


class LinkState:
    """The per-area adjacency graph (reference: openr/decision/LinkState †).

    Semantics preserved from the reference:
      * **Bidirectional check**: a directed edge u→v is usable only if v also
        reports an adjacency back to u (otherwise a half-up link would
        blackhole traffic). reference: LinkState topology construction †.
      * **Link overload** (adjacency.is_overloaded / metric override): the
        adjacency is excluded from SPF.
      * **Node overload**: an overloaded node is never used for *transit*
        (edges out of it are masked for every SPF root except itself);
        it remains reachable as a destination. reference: SpfSolver
        `nodeOverloaded` handling †.
    """

    def __init__(self, area: str = DEFAULT_AREA):
        self.area = area
        self._adj_dbs: dict[str, AdjacencyDatabase] = {}
        # monotonic topology revision: bumped on every APPLIED mutation
        # (update/delete that returned True) and carried by snapshots.
        # Decision's dirty-scoped rebuild keys its per-area solve cache
        # on this: a cached SolveArtifact is only reused while the
        # revision still matches, so any out-of-band mutation (one that
        # bypassed the publication path's dirt tracking) falls back to
        # a full rebuild instead of silently reusing a stale solve.
        self.rev = 0
        # CSR cache cell [base, patched, patched_upto], SHARED with
        # snapshots: a snapshot that builds the base CSR — or advances
        # the patched view — off-thread publishes it back through the
        # cell, so the live object (and later snapshots of the same
        # topology) reuse it. The patch state MUST live here and not on
        # the instance: rebuilds run on per-rebuild snapshots, and
        # instance-held progress would never propagate back — every
        # rebuild would re-apply the whole accumulated pending list
        # (observed: to_csr cost growing linearly over a churn epoch,
        # ~16 ms/cycle at steady state; round-5 profile). Mutation
        # replaces the cell instead of clearing it, so snapshots taken
        # before a structural change keep their own still-valid cache;
        # within one cell only the (serialized) rebuild thread writes
        # slots 1-2.
        self._csr_cell: list = [None, None, 0]
        # metric-only changes since the base CSR in the cell: applied
        # copy-on-write at to_csr() time (one array copy per solve, not
        # per flap), so churn never pays the O(E) python rebuild.
        # Rebound (never mutated in place) so snapshots stay consistent
        # — which also keeps cell[2] meaningful across snapshots: the
        # rebinding append preserves the prefix, so an index into one
        # snapshot's list addresses the same flaps in every later one.
        self._pending: list[tuple[str, Adjacency]] = []

    # ---- mutation ---------------------------------------------------------

    def update_adjacency_db(self, db: AdjacencyDatabase) -> bool:
        """Insert/replace a node's adjacency database.

        Returns True if the topology changed (triggers SPF recompute —
        the reference returns a LinkStateChange bitset; we collapse to bool).
        """
        return self.update_adjacency_db_delta(db)[0]

    def update_adjacency_db_delta(
        self, db: AdjacencyDatabase
    ) -> tuple[bool, list[tuple[str, str]] | None]:
        """Insert/replace a node's adjacency database, reporting the
        change *shape*: (changed, pairs) where `pairs` is the list of
        directed (node, neighbor) edges whose metric (or rtt) changed
        when the update was METRIC-ONLY, or None for any structural
        change (adjacency set, overload bits, labels, weights, first
        insert). Decision's topology-delta rebuild classifier consumes
        the pairs; everything else keeps the plain bool contract via
        `update_adjacency_db`."""
        old = self._adj_dbs.get(db.this_node_name)
        if old == db:
            return False, []
        self._adj_dbs[db.this_node_name] = db
        self.rev += 1
        # computed unconditionally (not only when a CSR base is cached):
        # the dirt classifier needs the metric-only verdict even before
        # the first to_csr() / on the oracle path, which never builds one
        delta = _metric_only_delta(old, db) if old is not None else None
        pairs = (
            [(db.this_node_name, a.other_node_name) for a in delta]
            if delta is not None
            else None
        )
        base = self._csr_cell[0]
        if base is not None and delta is not None:
            if (
                len(self._pending) + len(delta)
                <= max(64, base.num_edges // 8)  # compaction cap
            ):
                self._pending = self._pending + [
                    (db.this_node_name, a) for a in delta
                ]
                # cell's patched view stays: to_csr applies the suffix
                return True, pairs
        self._csr_cell = [None, None, 0]
        self._pending = []
        return True, pairs

    def delete_adjacency_db(self, node: str) -> bool:
        if node in self._adj_dbs:
            del self._adj_dbs[node]
            self.rev += 1
            self._csr_cell = [None, None, 0]
            self._pending = []
            return True
        return False

    def snapshot(self) -> "LinkState":
        """O(V) consistent copy for off-thread solves: the dict is copied,
        the AdjacencyDatabase values are frozen, and the CSR cache cell is
        shared — a CSR built on the snapshot (off-thread) becomes visible
        to the live object until the next topology change."""
        snap = LinkState(self.area)
        snap._adj_dbs = dict(self._adj_dbs)
        snap.rev = self.rev
        snap._csr_cell = self._csr_cell
        # _pending is rebound on mutation, never mutated, so sharing
        # the current reference is race-free; the patched view travels
        # in the shared cell
        snap._pending = self._pending
        return snap

    # ---- queries ----------------------------------------------------------

    @property
    def nodes(self) -> list[str]:
        return sorted(self._adj_dbs)

    def adjacency_db(self, node: str) -> AdjacencyDatabase | None:
        return self._adj_dbs.get(node)

    def is_node_overloaded(self, node: str) -> bool:
        db = self._adj_dbs.get(node)
        return bool(db and db.is_overloaded)

    def link_drained_by_peer(self, me: str, adj) -> bool:
        """Whether the far side of `me`'s adjacency has soft-drained
        the link (its matching reverse adjacency is_overloaded) — a
        drain from either side removes BOTH directions (reference:
        setInterfaceOverload †; same rule as build_csr)."""
        db = self._adj_dbs.get(adj.other_node_name)
        if db is None:
            return False
        return any(
            x.if_name == adj.other_if_name
            and x.other_node_name == me
            and x.is_overloaded
            for x in db.adjacencies
        )

    def node_label(self, node: str) -> int:
        db = self._adj_dbs.get(node)
        return db.node_label if db else 0

    def effective_metric(self, u: str, v: str) -> int | None:
        """Current directed SPF edge weight u→v — min clamped metric over
        the usable parallel adjacencies — or None when no usable edge
        exists. Same usability rules as `build_csr`/`build_adjacency`
        (bidirectional check, either-side drain, METRIC_MAX clamp), but
        O(deg) for ONE pair instead of O(E) for the graph: the
        topology-delta warm start resolves each flapped pair's new
        weight through this."""
        db = self._adj_dbs.get(u)
        dbv = self._adj_dbs.get(v)
        if db is None or dbv is None:
            return None
        if not any(x.other_node_name == u for x in dbv.adjacencies):
            return None  # bidirectional check failed
        best: int | None = None
        for a in db.adjacencies:
            if a.other_node_name != v or a.is_overloaded:
                continue
            if self.link_drained_by_peer(u, a):
                continue
            m = min(int(a.metric), METRIC_MAX)
            if best is None or m < best:
                best = m
        return best

    # ---- CSR materialization ---------------------------------------------

    def to_csr(self) -> CsrGraph:
        """Build (or return cached) padded CSR arrays for the solver.

        With metric-only churn pending, returns a copy-on-write patched
        view of the cached base — O(E) numpy copies + O(patches) fixups
        instead of the O(E) python rebuild — carrying the cumulative
        patch journal for the solver's device-array cache.
        """
        cell = self._csr_cell
        if cell[0] is None:
            cell[0] = self._build_csr()
            cell[1], cell[2] = None, 0
            self._pending = []
        base = cell[0]
        pending = self._pending  # rebound-on-append: stable view
        if not pending:
            return base
        patched, upto = cell[1], cell[2]
        if patched is None:
            patched, upto = self._apply_pending(base, pending), 0
        elif upto < len(pending):
            # incremental: patch only the suffix that arrived since the
            # last materialization — under sustained metric churn this
            # keeps per-rebuild host cost O(new flaps), not O(all
            # accumulated flaps since the last structural rebuild).
            # Progress is published through the shared cell so the NEXT
            # rebuild's snapshot continues from here.
            patched = self._apply_pending(patched, pending[upto:])
        elif upto > len(pending):
            # a cell advanced past this snapshot's pending view (a
            # newer rebuild ran concurrently — not the serialized
            # production flow): the patched CSR is ahead of this
            # snapshot; rebuild from base for a consistent view without
            # touching the shared progress
            return self._apply_pending(base, pending)
        cell[1], cell[2] = patched, len(pending)
        return patched

    def _apply_pending(
        self, base: CsrGraph, pending: list[tuple[str, Adjacency]]
    ) -> CsrGraph:
        new_metric = base.edge_metric.copy()
        overrides = dict(base.adj_overrides)  # small: churned edges only
        dense = base._dense
        wgt = dense[1].copy() if dense is not None else None
        touched: dict[tuple[int, int], list[list]] = {}
        for node, adj in pending:
            u = base.name_to_id.get(node)
            w = base.name_to_id.get(adj.other_node_name)
            if u is None or w is None:
                continue
            key = (u, w)
            if key not in base.edge_index:
                continue  # edge unusable in base (one-sided/overloaded)
            lst = touched.get(key)
            if lst is None:
                lst = touched[key] = [list(d) for d in base.details(*key)]
            for d in lst:
                if d[0] == adj.if_name and d[4] == adj.other_if_name:
                    d[1] = int(adj.metric)
        journal = list(base.patches)
        for key, lst in touched.items():
            overrides[key] = [tuple(d) for d in lst]
            m = min(min(d[1] for d in lst), METRIC_MAX)
            idx = base.edge_index[key]
            new_metric[idx] = m
            col = base.dense_col(idx, key[1])
            if wgt is not None:
                wgt[key[1], col] = m
            journal.append(MetricPatch(idx, key[1], col, int(m)))
        return replace(
            base,
            edge_metric=new_metric,
            adj_overrides=overrides,
            _dense=(dense[0], wgt) if dense is not None else None,
            version=next(_csr_version),
            patches=tuple(journal),
        )

    def _build_csr(self) -> CsrGraph:
        names = sorted(self._adj_dbs)  # deterministic interning
        name_to_id = {n: i for i, n in enumerate(names)}
        v = len(names)

        # Directed adjacency index for the bidirectional check, plus
        # the drained-link endpoints: an overloaded adjacency drains
        # BOTH directions of that one link (reference:
        # setInterfaceOverload † — maintenance soft-drain), identified
        # from the far side as (advertiser, advertiser's if_name) ==
        # our (other_node_name, other_if_name). Parallel links between
        # the same pair drain independently.
        has_reverse: set[tuple[str, str]] = set()
        drained: set[tuple[str, str]] = set()
        for node, db in self._adj_dbs.items():
            for adj in db.adjacencies:
                has_reverse.add((node, adj.other_node_name))
                if adj.is_overloaded:
                    drained.add((node, adj.if_name))

        srcs: list[int] = []
        dsts: list[int] = []
        metrics: list[int] = []
        adj_details: dict[tuple[int, int], list] = {}
        for node in names:
            db = self._adj_dbs[node]
            u = name_to_id[node]
            for adj in db.adjacencies:
                if adj.other_node_name not in name_to_id:
                    continue  # neighbor's adj db not yet received
                if (adj.other_node_name, node) not in has_reverse:
                    continue  # bidirectional check failed
                if adj.is_overloaded or (
                    adj.other_node_name, adj.other_if_name
                ) in drained:
                    continue  # drained link (either side, both dirs)
                w = name_to_id[adj.other_node_name]
                key = (u, w)
                detail = (
                    adj.if_name,
                    int(adj.metric),
                    int(adj.weight),
                    int(adj.adj_label),
                    adj.other_if_name,
                )
                # parallel links: SPF uses the min metric; all parallel
                # interfaces at min metric become ECMP nexthops
                adj_details.setdefault(key, []).append(detail)
                srcs.append(u)
                dsts.append(w)
                metrics.append(int(adj.metric))

        # Collapse parallel edges to min-metric (solver-side); details kept.
        edge_best: dict[tuple[int, int], int] = {}
        for s, d, m in zip(srcs, dsts, metrics):
            key = (s, d)
            if key not in edge_best or m < edge_best[key]:
                edge_best[key] = m
        e = len(edge_best)

        vp = pad_bucket(max(v, 1) + 1)  # +1 dead slot for padding edges
        ep = pad_bucket(max(e, 1), minimum=128)

        edge_src = np.zeros(ep, dtype=np.int32)
        edge_dst = np.full(ep, vp - 1, dtype=np.int32)  # dead slot
        edge_metric = np.full(ep, INF_METRIC, dtype=np.int32)

        # Sort by destination for contiguous segment reduction.
        items = sorted(edge_best.items(), key=lambda kv: (kv[0][1], kv[0][0]))
        edge_index: dict[tuple[int, int], int] = {}
        for i, ((s, d), m) in enumerate(items):
            edge_src[i] = s
            edge_dst[i] = d
            edge_metric[i] = min(m, METRIC_MAX)
            edge_index[(s, d)] = i

        node_overloaded = np.zeros(vp, dtype=bool)
        node_mask = np.zeros(vp, dtype=bool)
        for n, i in name_to_id.items():
            node_mask[i] = True
            node_overloaded[i] = self._adj_dbs[n].is_overloaded

        ver = next(_csr_version)
        return CsrGraph(
            num_nodes=v,
            num_edges=e,
            edge_src=edge_src,
            edge_dst=edge_dst,
            edge_metric=edge_metric,
            node_overloaded=node_overloaded,
            node_mask=node_mask,
            node_names=names,
            adj_details=adj_details,
            name_to_id=name_to_id,
            edge_index=edge_index,
            version=ver,
            base_version=ver,
        )


class PrefixState:
    """prefix → {advertising node → PrefixEntry} for one area.

    reference: openr/decision/PrefixState.{h,cpp} †.
    """

    def __init__(self, area: str = DEFAULT_AREA):
        self.area = area
        self._entries: dict[IpPrefix, dict[str, PrefixEntry]] = {}
        # bumped on every mutation: keys the solver-view cache below.
        # The cache lives in a shared one-cell list (same pattern as
        # LinkState._csr_cell): per-rebuild snapshots share the cell, so
        # a view built during an off-thread solve is visible to the live
        # object and later snapshots — without sharing, the production
        # path (Decision snapshots PrefixState per rebuild) would build
        # the view on a throwaway copy every time.
        self._rev = 0
        self._view_cell: list = [None]
        # lineage id: distinguishes independent PrefixState instances
        # whose per-instance _rev counters could coincide. Snapshots
        # (copy-style constructors) inherit it, so within one lineage
        # the solver_view gen is content-stable; across instances it
        # can never collide.
        self._lineage = next(_PS_LINEAGE)
        # the frozen copy snapshot() handed out last: while _rev stands
        # where it was built, the same object serves again
        self._snap: PrefixState | None = None

    def update_prefix_db(self, db: PrefixDatabase) -> set[IpPrefix]:
        """Apply a node's prefix advertisement; returns changed prefixes."""
        changed: set[IpPrefix] = set()
        node = db.this_node_name
        if db.delete_prefix:
            for entry in db.prefix_entries:
                if self.withdraw(node, entry.prefix):
                    changed.add(entry.prefix)
            return changed
        for entry in db.prefix_entries:
            per_node = self._entries.get(entry.prefix, {})
            if per_node.get(node) != entry:
                # rebind, never write in place: snapshots share the
                # per-prefix dicts (see snapshot())
                self._entries[entry.prefix] = {**per_node, node: entry}
                changed.add(entry.prefix)
        if changed:
            self._rev += 1
        return changed

    def snapshot(self) -> "PrefixState":
        """Frozen view for off-thread solves, shared by revision.

        While ``_rev`` stands this returns the object it returned last
        time. When ``_rev`` has moved it builds one: a copy of the outer
        dict only. The per-prefix dicts are values, shared by the live
        object and every snapshot: the three writers (update_prefix_db,
        withdraw, withdraw_node) rebind ``_entries[prefix]`` to a new
        dict and never write one in place, and nothing else may write
        ``_entries`` once a snapshot exists. The live outer dict is
        never handed out, so a later mutation cannot show in a snapshot.
        A snapshot is read-only.
        """
        if not self.snapshot_is_current:
            snap = PrefixState(self.area)
            snap._entries = dict(self._entries)
            snap._rev = self._rev
            snap._view_cell = self._view_cell  # shared cell, rev-keyed
            snap._lineage = self._lineage  # same lineage: gen stays stable
            self._snap = snap
        return self._snap

    @property
    def snapshot_is_current(self) -> bool:
        """Whether snapshot() would return the object it returned last
        (nothing changed since) rather than build one."""
        return self._snap is not None and self._snap._rev == self._rev

    def election_view(self, name_to_id: dict, base_version: int):
        """Cached columnar election classification for RIB assembly
        (:class:`openr_tpu.decision.election.ElectView`).

        Splits prefixes into the vectorized-electable shapes — "plain"
        (one known advertiser, SP_ECMP, no constraints) with numpy
        originator-id arrays, and "multi" (anycast ECMP: 2+ advertisers,
        all plain-shaped) as the prefix→advertiser matrix the batched
        election consumes — and everything else, which keeps the scalar
        general path. Cached on (prefix rev, topology base): under
        metric-only churn neither changes, so steady-state rebuilds
        skip the O(P) classification entirely.

        ``gen`` is a generation token unique to (instance lineage,
        prefix rev, topology base): within one PrefixState lineage it
        changes iff the view could, and it can never collide across
        independent instances (the lineage id), so cross-rebuild caches
        may key row indices into the view arrays on it.
        """
        key = (self._lineage, self._rev, base_version)
        cached = self._view_cell[0]
        if cached is not None and cached[0] == key:
            return cached[1]
        from openr_tpu.decision.election import build_elect_view

        view = build_elect_view(self._entries, name_to_id, key)
        self._view_cell[0] = (key, view)
        return view

    def solver_view(self, name_to_id: dict, base_version: int):
        """Legacy tuple facade over :meth:`election_view`: returns
        (plain_prefixes, plain_nodes, plain_entries, orig_ids [P]
        int64, complex_items, gen) with the multi-advertiser electable
        prefixes folded back into complex_items — the pre-election
        contract, kept for callers that only understand the plain/
        complex split."""
        v = self.election_view(name_to_id, base_version)
        complex_items = v.complex_items
        if v.multi is not None:
            from openr_tpu.decision.election import multi_items

            complex_items = sorted(complex_items + multi_items(v.multi))
        return (v.plain_p, v.plain_n, v.plain_e, v.orig, complex_items, v.gen)

    def withdraw(self, node: str, prefix: IpPrefix) -> bool:
        per_node = self._entries.get(prefix)
        if per_node and node in per_node:
            if len(per_node) == 1:
                del self._entries[prefix]
            else:
                # rebind, never write in place (see snapshot())
                self._entries[prefix] = {
                    n: e for n, e in per_node.items() if n != node
                }
            self._rev += 1
            return True
        return False

    def withdraw_node(self, node: str) -> set[IpPrefix]:
        """Remove everything `node` advertises (node left the topology)."""
        changed: set[IpPrefix] = set()
        for prefix in list(self._entries):  # orlint: disable=OR013 — structural node-withdraw sweep (node left the topology), event-driven, not steady-state churn
            if self.withdraw(node, prefix):
                changed.add(prefix)
        return changed

    @property
    def rev(self) -> int:
        """Monotonic mutation revision (mirrors LinkState.rev): the
        dirty-scoped rebuild uses it to prove a no-dirt area really is
        unchanged before reusing its cached per-area RIB."""
        return self._rev

    @property
    def prefixes(self) -> dict[IpPrefix, dict[str, PrefixEntry]]:
        return self._entries

    def advertisers(self, prefix: IpPrefix) -> dict[str, PrefixEntry]:
        return self._entries.get(prefix, {})
