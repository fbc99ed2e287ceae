"""Synthetic topology generators for tests, benchmarks, and the emulator.

The reference builds these inline in its tests/benchmarks
(reference: openr/decision/tests/DecisionTest.cpp † grid/ring helpers,
openr/decision/tests/DecisionBenchmark.cpp † grid topologies,
openr/tests/utils/Utils.cpp † createAdjDb/createPrefixDb). Centralized here
because bench.py and the emulator share them.

Every generator returns `(adj_dbs, prefix_dbs)`: one AdjacencyDatabase per
node (bidirectional adjacencies, integer metrics) and one PrefixDatabase per
node advertising that node's loopback prefix.
"""

from __future__ import annotations

import numpy as np

from openr_tpu.types.network import IpPrefix
from openr_tpu.types.topology import (
    Adjacency,
    AdjacencyDatabase,
    PrefixDatabase,
    PrefixEntry,
)


def node_name(i: int) -> str:
    return f"node-{i}"


def loopback(i: int) -> IpPrefix:
    """Unique /32 per node out of 10.0.0.0/8 (supports ~16M nodes)."""
    return IpPrefix.make(f"10.{(i >> 16) & 0xFF}.{(i >> 8) & 0xFF}.{i & 0xFF}/32")


def _mk_dbs(
    n: int,
    edges: list[tuple[int, int, int]],
    area: str = "0",
) -> tuple[list[AdjacencyDatabase], list[PrefixDatabase]]:
    """edges: directed (u, v, metric); callers emit both directions."""
    adjs: dict[int, list[Adjacency]] = {i: [] for i in range(n)}
    for u, v, m in edges:
        adjs[u].append(
            Adjacency(
                other_node_name=node_name(v),
                if_name=f"if_{u}_{v}",
                other_if_name=f"if_{v}_{u}",
                metric=m,
            )
        )
    adj_dbs = [
        AdjacencyDatabase(
            this_node_name=node_name(i),
            adjacencies=tuple(adjs[i]),
            node_label=101 + i,
            area=area,
        )
        for i in range(n)
    ]
    prefix_dbs = [
        PrefixDatabase(
            this_node_name=node_name(i),
            prefix_entries=(PrefixEntry(prefix=loopback(i)),),
            area=area,
        )
        for i in range(n)
    ]
    return adj_dbs, prefix_dbs


def ring(n: int, metric: int = 1):
    """Ring of n nodes (reference test analogue: DecisionTest ring cases †)."""
    edges = []
    for i in range(n):
        j = (i + 1) % n
        edges.append((i, j, metric))
        edges.append((j, i, metric))
    return _mk_dbs(n, edges)


def grid(rows: int, cols: int, metric: int = 1):
    """rows×cols grid (reference: DecisionBenchmark grid topologies †)."""
    edges = []

    def nid(r, c):
        return r * cols + c

    for r in range(rows):
        for c in range(cols):
            if c + 1 < cols:
                a, b = nid(r, c), nid(r, c + 1)
                edges += [(a, b, metric), (b, a, metric)]
            if r + 1 < rows:
                a, b = nid(r, c), nid(r + 1, c)
                edges += [(a, b, metric), (b, a, metric)]
    return _mk_dbs(rows * cols, edges)


def full_mesh(n: int, metric: int = 1):
    edges = []
    for i in range(n):
        for j in range(i + 1, n):
            edges += [(i, j, metric), (j, i, metric)]
    return _mk_dbs(n, edges)


def fat_tree(k: int = 4, metric: int = 1):
    """3-tier k-ary fat-tree (BASELINE config 1 uses ~100 nodes ⇒ k=8 is 208).

    Layout: (k/2)^2 core switches; k pods, each with k/2 agg + k/2 tor.
    Every tor connects to every agg in its pod; agg i in each pod connects to
    core switches [i*(k/2), (i+1)*(k/2)).
    """
    assert k % 2 == 0
    half = k // 2
    n_core = half * half
    n_agg = k * half
    n_tor = k * half
    n = n_core + n_agg + n_tor

    def core_id(i):
        return i

    def agg_id(pod, i):
        return n_core + pod * half + i

    def tor_id(pod, i):
        return n_core + n_agg + pod * half + i

    edges = []
    for pod in range(k):
        for a in range(half):
            for t in range(half):
                u, v = agg_id(pod, a), tor_id(pod, t)
                edges += [(u, v, metric), (v, u, metric)]
            for c in range(half):
                u, v = agg_id(pod, a), core_id(a * half + c)
                edges += [(u, v, metric), (v, u, metric)]
    return _mk_dbs(n, edges)


def fat_tree_pod(k: int = 4, pods: int = 1, metric: int = 1):
    """Pod-granular fat-tree slice: the (k/2)^2 core switches plus
    ``pods`` pods of k/2 agg + k/2 tor each — the deployment unit a
    cluster grows by (a full ``fat_tree(k)`` is ``pods=k``). Lets the
    multi-process harness pick exact fleet sizes ((k/2)^2 + pods*k
    nodes: k=4, pods=3 -> 16; k=4, pods=15 -> 64) while keeping real
    fat-tree wiring: tor<->agg full bipartite per pod, agg i uplinked
    to cores [i*(k/2), (i+1)*(k/2))."""
    assert k % 2 == 0 and k >= 2, k
    assert 1 <= pods <= k, (pods, k)
    half = k // 2
    n_core = half * half
    n = n_core + pods * k

    def agg_id(pod, i):
        return n_core + pod * k + i

    def tor_id(pod, i):
        return n_core + pod * k + half + i

    edges = []
    for pod in range(pods):
        for a in range(half):
            for t in range(half):
                u, v = agg_id(pod, a), tor_id(pod, t)
                edges += [(u, v, metric), (v, u, metric)]
            for c in range(half):
                u, v = agg_id(pod, a), a * half + c
                edges += [(u, v, metric), (v, u, metric)]
    return _mk_dbs(n, edges)


def wan_like(
    n: int,
    seed: int = 0,
    core_frac: float = 0.25,
    metric_lo: int = 10,
    metric_hi: int = 100,
):
    """WAN-ish topology: a ring of core POPs with seeded long-haul
    chords (express links), every remaining node a stub site dual-homed
    to two distinct core POPs. Heterogeneous seeded metrics in
    [metric_lo, metric_hi] model circuit latency — unlike the
    uniform-metric DC families, SPF here has real tie-free geography.
    Deterministic under (n, seed): same arguments, same graph."""
    assert n >= 4, n
    rng = np.random.default_rng(seed)
    n_core = max(3, int(n * core_frac))
    n_core = min(n_core, n)
    n_stub = n - n_core

    def m():
        return int(rng.integers(metric_lo, metric_hi + 1))

    edges = []
    seen: set[tuple[int, int]] = set()

    def add(u, v, w):
        if u == v or (u, v) in seen:
            return
        seen.add((u, v))
        seen.add((v, u))
        edges.append((u, v, w))
        edges.append((v, u, w))

    for i in range(n_core):  # core POP ring
        add(i, (i + 1) % n_core, m())
    # express chords: ~1 per 3 core POPs, endpoints seeded
    for _ in range(max(1, n_core // 3)):
        u = int(rng.integers(0, n_core))
        v = int(rng.integers(0, n_core))
        add(u, v, m())
    for s in range(n_stub):  # dual-homed stub sites
        sid = n_core + s
        h = int(rng.integers(0, n_core))
        add(sid, h, m())
        if n_core > 1:
            add(sid, (h + 1) % n_core, m())
    return _mk_dbs(n, edges)


def hub_and_spoke(
    hubs: int = 2, spokes: int = 8, metric: int = 1, spoke_metric: int = 10
):
    """``hubs`` fully-meshed hub routers; each spoke dual-homed to a
    primary hub (round-robin) and the next hub over (single-homed when
    hubs == 1). The degree-skew extreme the flooding mesh sees in
    access/aggregation networks: hub fan-out grows with the spoke
    count while every spoke keeps degree <= 2."""
    assert hubs >= 1 and spokes >= 0, (hubs, spokes)
    edges = []
    for i in range(hubs):
        for j in range(i + 1, hubs):
            edges += [(i, j, metric), (j, i, metric)]
    for s in range(spokes):
        sid = hubs + s
        h = s % hubs
        edges += [(sid, h, spoke_metric), (h, sid, spoke_metric)]
        if hubs > 1:
            b = (h + 1) % hubs
            edges += [(sid, b, spoke_metric), (b, sid, spoke_metric)]
    return _mk_dbs(hubs + spokes, edges)


def edges_of(adj_dbs) -> list[tuple[str, str]]:
    """Undirected (name_a, name_b) pairs of a generator's adjacency
    databases — the wiring list the emulator Cluster / multi-process
    supervisor consume (each pair becomes one point-to-point link)."""
    pairs: set[tuple[str, str]] = set()
    for db in adj_dbs:
        for adj in db.adjacencies:
            a, b = db.this_node_name, adj.other_node_name
            pairs.add((a, b) if a < b else (b, a))
    return sorted(pairs)


def erdos_renyi_csr(
    n: int, avg_degree: int = 10, seed: int = 0, max_metric: int = 16
):
    """Large-scale variant that skips dataclasses entirely: returns padded
    CSR arrays (edge_src, edge_dst, edge_metric, padded_nodes) directly.
    Used by bench.py for the 100k-node/1M-edge BASELINE config, where
    building millions of Adjacency objects would dominate the benchmark
    setup. Same graph family as `erdos_renyi` (backbone ring + chords).
    """
    from openr_tpu.common.constants import DIST_INF
    from openr_tpu.decision.linkstate import pad_bucket

    rng = np.random.default_rng(seed)
    target = n * avg_degree // 2
    ring_u = np.arange(n, dtype=np.int64)
    ring_v = (ring_u + 1) % n
    us = rng.integers(0, n, size=int(2.2 * target))
    vs = rng.integers(0, n, size=int(2.2 * target))
    keep = us != vs
    us, vs = us[keep], vs[keep]
    u_all = np.concatenate([ring_u, us])
    v_all = np.concatenate([ring_v, vs])
    lo, hi = np.minimum(u_all, v_all), np.maximum(u_all, v_all)
    key = lo * n + hi
    _, first_idx = np.unique(key, return_index=True)
    first_idx = np.sort(first_idx)[: target + n]
    lo, hi = lo[first_idx], hi[first_idx]
    metric = rng.integers(1, max_metric + 1, size=lo.shape[0])

    src = np.concatenate([lo, hi]).astype(np.int32)
    dst = np.concatenate([hi, lo]).astype(np.int32)
    met = np.concatenate([metric, metric]).astype(np.int32)
    order = np.argsort(dst, kind="stable")
    src, dst, met = src[order], dst[order], met[order]

    e = src.shape[0]
    vp = pad_bucket(n + 1)
    ep = pad_bucket(e, minimum=128)
    edge_src = np.zeros(ep, dtype=np.int32)
    edge_dst = np.full(ep, vp - 1, dtype=np.int32)
    edge_metric = np.full(ep, DIST_INF, dtype=np.int32)
    edge_src[:e] = src
    edge_dst[:e] = dst
    edge_metric[:e] = met
    return edge_src, edge_dst, edge_metric, vp, n, e


def erdos_renyi(n: int, avg_degree: int = 10, seed: int = 0, max_metric: int = 16):
    """Random graph with ~n*avg_degree/2 undirected edges (BASELINE config 3).

    Guaranteed connected-ish via a Hamiltonian backbone ring plus random
    chords; metrics uniform in [1, max_metric].
    """
    rng = np.random.default_rng(seed)
    seen = set()
    edges = []

    def add(u, v, m):
        if u == v or (u, v) in seen:
            return
        seen.add((u, v))
        seen.add((v, u))
        edges.append((u, v, m))
        edges.append((v, u, m))

    for i in range(n):  # backbone ring keeps it connected
        add(i, (i + 1) % n, int(rng.integers(1, max_metric + 1)))
    target = n * avg_degree // 2
    us = rng.integers(0, n, size=3 * target)
    vs = rng.integers(0, n, size=3 * target)
    ms = rng.integers(1, max_metric + 1, size=3 * target)
    for u, v, m in zip(us, vs, ms):
        if len(seen) // 2 >= target:
            break
        add(int(u), int(v), int(m))
    return _mk_dbs(n, edges)


class LsdbView:
    """LinkState-compatible read surface over a directly-constructed
    CsrGraph, for benchmark-scale topologies (erdos_renyi_lsdb): the
    solver's RIB path only reads `to_csr()`, `area`, `nodes`,
    `node_label()` and `adjacency_db()`, so 100k-node graphs can skip
    building millions of Adjacency dataclasses."""

    def __init__(self, csr, area: str = "0"):
        self._csr = csr
        self.area = area
        self.nodes = list(csr.node_names)
        self._labels = {
            s: 101 + i for i, s in enumerate(csr.node_names)
        }
        self._out_index = None  # lazy src-sorted edge index

    def to_csr(self):
        return self._csr

    def node_label(self, node: str) -> int:
        return self._labels[node]

    def is_node_overloaded(self, node: str) -> bool:
        nid = self._csr.name_to_id.get(node)
        return bool(
            nid is not None and self._csr.node_overloaded[nid]
        )

    def adjacency_db(self, node: str):
        """Synthesized on demand from the CSR arrays (same naming
        convention as the adj_details the builder populates), so the
        oracle and the MPLS adjacency section see a full LinkState
        surface. No per-link labels (adj_label=0)."""
        from openr_tpu.types.topology import Adjacency, AdjacencyDatabase

        csr = self._csr
        nid = csr.name_to_id.get(node)
        if nid is None:
            return None
        if self._out_index is None:
            from openr_tpu.common.constants import DIST_INF

            valid = csr.edge_metric < DIST_INF
            src = csr.edge_src[valid]
            order = np.argsort(src, kind="stable")
            starts = np.searchsorted(
                src[order], np.arange(csr.padded_nodes + 1)
            )
            self._out_index = (
                csr.edge_dst[valid][order],
                csr.edge_metric[valid][order],
                starts,
            )
        dst, met, starts = self._out_index
        lo, hi = starts[nid], starts[nid + 1]
        adjs = tuple(
            Adjacency(
                other_node_name=csr.node_names[int(d)],
                if_name=f"if_{nid}_{int(d)}",
                other_if_name=f"if_{int(d)}_{nid}",
                metric=int(m),
            )
            for d, m in zip(dst[lo:hi], met[lo:hi])
        )
        return AdjacencyDatabase(
            this_node_name=node,
            adjacencies=adjs,
            node_label=self._labels[node],
            area=self.area,
        )


def erdos_renyi_lsdb(
    n: int, avg_degree: int = 20, seed: int = 0, max_metric: int = 64
):
    """Benchmark-scale LSDB: (ls_view, prefix_state, csr).

    The CsrGraph is assembled directly from the `erdos_renyi_csr` arrays
    (adj_details populated only for node-0, the benchmark vantage point
    — the solver reads other nodes' details only for its own nexthop
    slots); the PrefixState advertises one loopback per node, the same
    shape the production PrefixManager floods.
    """
    from openr_tpu.decision import linkstate as _lsmod
    from openr_tpu.decision.linkstate import CsrGraph, PrefixState
    from openr_tpu.types.topology import PrefixEntry

    edge_src, edge_dst, edge_metric, vp, nn, e = erdos_renyi_csr(
        n, avg_degree=avg_degree, seed=seed, max_metric=max_metric
    )
    names = [node_name(i) for i in range(nn)]
    name_to_id = {s: i for i, s in enumerate(names)}
    valid = edge_metric < np.int32(1 << 30)
    my = 0
    adj_details: dict = {}
    out_mask = (edge_src == my) & valid
    for d, m in zip(edge_dst[out_mask], edge_metric[out_mask]):
        adj_details.setdefault((my, int(d)), []).append(
            (f"if_{my}_{int(d)}", int(m), 0, 0, f"if_{int(d)}_{my}")
        )
    ver = next(_lsmod._csr_version)
    csr = CsrGraph(
        num_nodes=nn,
        num_edges=int(e),
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_metric=edge_metric,
        node_overloaded=np.zeros(vp, dtype=bool),
        node_mask=np.arange(vp) < nn,
        node_names=names,
        adj_details=adj_details,
        name_to_id=name_to_id,
        version=ver,
        base_version=ver,
        structure_version=ver,
    )
    ps = PrefixState()
    for i, s in enumerate(names):
        entry = PrefixEntry(prefix=loopback(i))
        ps._entries[entry.prefix] = {s: entry}
    return LsdbView(csr), ps, csr


def ramp_prefix_state(
    names: list[str],
    n_prefixes: int,
    anycast_every: int = 0,
    base: str = "16.0.0.0",
) -> "object":
    """PrefixState with `n_prefixes` /32s advertised round-robin across
    `names[1:]` (node 0 is the bench vantage point — keeping it out of
    the advertiser set makes routes == prefixes exactly).

    Prefixes come from a PrefixRange (prefixmgr/ranges.py): string
    minting is integer arithmetic, no per-prefix ipaddress parse.
    With ``anycast_every`` = k > 0, every k-th prefix gains a second
    advertiser (equal metrics — an ECMP-tie anycast), exercising the
    multi-advertiser election matrix at scale.
    """
    from openr_tpu.decision.linkstate import PrefixState
    from openr_tpu.prefixmgr.ranges import PrefixRange

    ps = PrefixState()
    rng = PrefixRange(base=base, plen=32, count=n_prefixes)
    adv = names[1:] or names
    n_adv = len(adv)
    entries = ps._entries
    for i in range(n_prefixes):
        e = rng.entry_at(i)
        per = {adv[i % n_adv]: e}
        if anycast_every and i % anycast_every == 0 and n_adv > 1:
            # the +1 offset is provably a DIFFERENT advertiser, so the
            # anycast count is exact (a pseudo-random second pick could
            # collide with the first and silently degrade to plain)
            per[adv[(i + 1) % n_adv]] = e
        entries[e.prefix] = per
    ps._rev += 1
    return ps
