"""TPU SPF kernel tests: the RIB-equivalence gate.

The contract (SURVEY §7 step 3): `TpuSpfSolver.compute_routes` output must
EQUAL the oracle's `compute_routes` — full RouteDatabase equality (nexthop
sets, metrics, MPLS actions) — across golden and randomized topologies,
including overload and unreachability scenarios. Runs on the CPU backend
with 8 virtual devices (conftest); the same code path runs on TPU.
"""

import numpy as np
import pytest

from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.oracle import compute_routes as oracle_routes
from openr_tpu.decision.oracle import run_spf
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.ops.spf import INF_DIST, pad_batch
from openr_tpu.types.topology import AdjacencyDatabase
from openr_tpu.utils import topogen


def _state(adj_dbs, prefix_dbs):
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    return ls, ps


def _overload(db: AdjacencyDatabase) -> AdjacencyDatabase:
    return AdjacencyDatabase(
        this_node_name=db.this_node_name,
        adjacencies=db.adjacencies,
        is_overloaded=True,
        node_label=db.node_label,
        area=db.area,
    )


def _assert_rib_equal(ls, ps, node):
    want = oracle_routes(ls, ps, node)
    # both engines must match the oracle exactly
    for kw in _engines():
        got = TpuSpfSolver(**kw).compute_routes(ls, ps, node)
        assert got.unicast_routes == want.unicast_routes, (node, kw)
        assert got.mpls_routes == want.mpls_routes, (node, kw)


def _engines():
    """The split kernel on the device and, where the .so is built, the
    native C++ radix-heap solver."""
    from openr_tpu.ops.native_spf import native_available

    engines = [dict(native_rib="off")]
    if native_available():
        engines.append(dict(native_rib="on"))
    return engines


def _split_dist(csr, roots):
    """[vp, B] distances from the split kernel as the solver runs it,
    roots padded to their bucket by repeating the first."""
    padded = np.full(pad_batch(len(roots)), roots[0], dtype=np.int32)
    padded[: len(roots)] = roots
    dist = TpuSpfSolver(native_rib="off")._solve_dist(csr, padded)
    return np.asarray(dist)[:, : len(roots)]


TOPOLOGIES = {
    "ring4": lambda: topogen.ring(4),
    "ring5": lambda: topogen.ring(5),
    "grid4x4": lambda: topogen.grid(4, 4),
    "fat_tree_k4": lambda: topogen.fat_tree(4),
    "er60": lambda: topogen.erdos_renyi(60, avg_degree=5, seed=7),
    "er40_weighted": lambda: topogen.erdos_renyi(40, avg_degree=4, seed=3, max_metric=1000),
}


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_rib_equivalence(name):
    adj_dbs, prefix_dbs = TOPOLOGIES[name]()
    ls, ps = _state(adj_dbs, prefix_dbs)
    # check several vantage points, not just node-0
    nodes = ls.nodes
    for node in {nodes[0], nodes[len(nodes) // 2], nodes[-1]}:
        _assert_rib_equal(ls, ps, node)


def test_rib_equivalence_with_overloaded_transit():
    adj_dbs, prefix_dbs = topogen.grid(4, 4)
    # overload two middle nodes — forces detours
    for i in (5, 10):
        adj_dbs[i] = _overload(adj_dbs[i])
    ls, ps = _state(adj_dbs, prefix_dbs)
    for node in ("node-0", "node-5", "node-15"):
        _assert_rib_equal(ls, ps, node)


def test_rib_equivalence_overloaded_self():
    adj_dbs, prefix_dbs = topogen.ring(6)
    adj_dbs[0] = _overload(adj_dbs[0])
    ls, ps = _state(adj_dbs, prefix_dbs)
    _assert_rib_equal(ls, ps, "node-0")  # overloaded root still routes out
    _assert_rib_equal(ls, ps, "node-3")


def test_rib_equivalence_partitioned():
    # two disjoint rings in one LSDB: routes only within the partition
    a_adj, a_pfx = topogen.ring(4)
    edges = [(0, 1, 1), (1, 0, 1), (1, 2, 1), (2, 1, 1)]
    b_adj, b_pfx = topogen._mk_dbs(3, edges)
    renamed_adj, renamed_pfx = [], []
    for db in b_adj:
        renamed_adj.append(
            AdjacencyDatabase(
                this_node_name="x-" + db.this_node_name,
                adjacencies=tuple(
                    type(a)(
                        other_node_name="x-" + a.other_node_name,
                        if_name=a.if_name,
                        other_if_name=a.other_if_name,
                        metric=a.metric,
                    )
                    for a in db.adjacencies
                ),
                node_label=db.node_label + 500,
            )
        )
    ls, ps = _state(a_adj + renamed_adj, a_pfx)
    _assert_rib_equal(ls, ps, "node-0")
    _assert_rib_equal(ls, ps, "x-node-0")


def test_kernel_dist_matches_oracle_random():
    """Raw distance matrix vs oracle Dijkstra on weighted random graphs,
    including overloaded transit nodes."""
    rng = np.random.default_rng(0)
    for seed in range(3):
        adj_dbs, _ = topogen.erdos_renyi(50, avg_degree=4, seed=seed, max_metric=64)
        over = rng.choice(50, size=5, replace=False)
        for i in over:
            adj_dbs[i] = _overload(adj_dbs[i])
        ls = LinkState()
        for db in adj_dbs:
            ls.update_adjacency_db(db)
        csr = ls.to_csr()
        roots = ls.nodes[::7]
        dist = _split_dist(csr, [csr.name_to_id[r] for r in roots])
        for col, root in enumerate(roots):
            res = run_spf(ls, root)
            for n, i in csr.name_to_id.items():
                want = res.dist.get(n)
                got = int(dist[i, col])
                if want is None:
                    assert got >= INF_DIST, (root, n)
                else:
                    assert got == want, (root, n)


def test_large_metrics_no_inversion():
    """Metrics in the millions (RTT-us style) must not be clamped into
    path-selection inversion (regression: old METRIC_MAX=2^20 clamp made a
    2x2.0M path beat a 3x1.2M path).

    Topology: 0→1→4 with metric 2,000,000 each (cost 4.0M) vs
    0→2→3→4 with metric 1,200,000 each (cost 3.6M — correct winner)."""
    edges = [
        (0, 1, 2_000_000), (1, 0, 2_000_000),
        (1, 4, 2_000_000), (4, 1, 2_000_000),
        (0, 2, 1_200_000), (2, 0, 1_200_000),
        (2, 3, 1_200_000), (3, 2, 1_200_000),
        (3, 4, 1_200_000), (4, 3, 1_200_000),
    ]
    adj_dbs, prefix_dbs = topogen._mk_dbs(5, edges)
    ls, ps = _state(adj_dbs, prefix_dbs)
    for kw in _engines():
        got = TpuSpfSolver(**kw).compute_routes(ls, ps, "node-0")
        r = got.unicast_routes[topogen.loopback(4)]
        assert r.igp_cost == 3_600_000, (kw, r.igp_cost)
        assert {nh.neighbor_node for nh in r.nexthops} == {"node-2"}
    _assert_rib_equal(ls, ps, "node-0")


def test_rib_equivalence_metric_above_clamp():
    """Metrics above METRIC_MAX are clamped identically by the kernel path
    and the oracle (regression: the first-hop identity must use the clamped
    metric or routes silently vanish at the clamp boundary)."""
    from openr_tpu.common.constants import METRIC_MAX

    adj_dbs, prefix_dbs = topogen.ring(4, metric=METRIC_MAX + 5)
    ls, ps = _state(adj_dbs, prefix_dbs)
    _assert_rib_equal(ls, ps, "node-0")
    want = oracle_routes(ls, ps, "node-0")
    assert want.unicast_routes  # routes must actually exist


def test_split_builder_bounds_mega_hub_blowup():
    """A star topology (one hub with huge degree) solves on split tables
    whose base width stays under the hub's degree, without materializing
    the V*D full-width tables."""
    n = 40
    edges = []
    for i in range(1, n):
        edges += [(0, i, 1), (i, 0, 1)]
    adj_dbs, prefix_dbs = topogen._mk_dbs(n, edges)
    ls, ps = _state(adj_dbs, prefix_dbs)
    csr = ls.to_csr()
    # native off so the batched path actually runs
    solver = TpuSpfSolver(native_rib="off")
    assert csr.dense_width() >= 32
    _ = solver.compute_routes(ls, ps, "node-1")
    assert csr._dense is None  # full-width tables were never built
    # pick_base_width: the hub's in-edges past the base overflow
    base_w = solver._dev[csr.base_version]["host"]["split"]["base_w"]
    assert base_w < n - 1
    _assert_rib_equal(ls, ps, "node-1")


def test_kernel_repeated_roots_and_padding():
    adj_dbs, _ = topogen.ring(4)
    ls = LinkState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    csr = ls.to_csr()
    dist = _split_dist(csr, [0, 0, 2, 2])
    assert (dist[:, 0] == dist[:, 1]).all()
    assert (dist[:, 2] == dist[:, 3]).all()
    assert dist[0, 0] == 0 and dist[2, 0] == 2
    # dead padding node slots stay unreachable
    assert (dist[csr.num_nodes :, :] >= INF_DIST).all()


def test_synthetic_bench_lsdb_matches_oracle():
    """bench.py's directly-constructed LSDB (topogen.erdos_renyi_lsdb,
    no AdjacencyDatabase objects) must drive compute_routes to the same
    RIB the oracle derives from the same view — validates the headline
    bench's full-RIB path end-to-end at a small scale."""
    from openr_tpu.ops.native_spf import native_available

    ls, ps, _csr = topogen.erdos_renyi_lsdb(
        300, avg_degree=6, seed=3, max_metric=32
    )
    want = oracle_routes(ls, ps, "node-0")
    assert len(want.unicast_routes) > 250  # connected-ish graph
    engines = [dict(native_rib="off")]
    if native_available():
        engines.append(dict(native_rib="on"))
    for kw in engines:
        got = TpuSpfSolver(**kw).compute_routes(ls, ps, "node-0")
        assert got.unicast_routes == want.unicast_routes, kw
        assert got.mpls_routes == want.mpls_routes, kw
