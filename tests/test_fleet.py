"""Fleet batched solve (decision/fleet.py): every node's RIB from one
device call must equal the per-node solver output exactly."""

from __future__ import annotations

import pytest

from openr_tpu.decision.fleet import compute_fleet_ribs
from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.types.topology import AdjacencyDatabase
from openr_tpu.utils import topogen


def _state(adj_dbs, prefix_dbs):
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    return ls, ps


@pytest.mark.parametrize(
    "topo",
    ["grid", "fat_tree", "er"],
)
def test_fleet_equals_per_node(topo):
    if topo == "grid":
        adj_dbs, prefix_dbs = topogen.grid(4, 4)
    elif topo == "fat_tree":
        adj_dbs, prefix_dbs = topogen.fat_tree(4)
    else:
        adj_dbs, prefix_dbs = topogen.erdos_renyi(
            40, avg_degree=4, seed=9, max_metric=16
        )
    ls, ps = _state(adj_dbs, prefix_dbs)
    fleet = compute_fleet_ribs(ls, ps)
    assert set(fleet) == set(ls.nodes)
    per_node = TpuSpfSolver(native_rib="off")
    for node in ls.nodes:
        want = per_node.compute_routes(ls, ps, node)
        got = fleet[node]
        assert got.unicast_routes == want.unicast_routes, node
        assert got.mpls_routes == want.mpls_routes, node


def test_fleet_with_overloads():
    adj_dbs, prefix_dbs = topogen.grid(4, 4)
    adj_dbs[5] = AdjacencyDatabase(
        this_node_name=adj_dbs[5].this_node_name,
        adjacencies=adj_dbs[5].adjacencies,
        is_overloaded=True,
        node_label=adj_dbs[5].node_label,
        area=adj_dbs[5].area,
    )
    ls, ps = _state(adj_dbs, prefix_dbs)
    fleet = compute_fleet_ribs(ls, ps)
    per_node = TpuSpfSolver(native_rib="off")
    for node in ("node-0", "node-5", "node-15"):
        want = per_node.compute_routes(ls, ps, node)
        assert fleet[node].unicast_routes == want.unicast_routes, node


def test_fleet_subset_and_unknown():
    adj_dbs, prefix_dbs = topogen.ring(5)
    ls, ps = _state(adj_dbs, prefix_dbs)
    fleet = compute_fleet_ribs(ls, ps, nodes=["node-1", "ghost"])
    assert set(fleet) == {"node-1"}
    want = TpuSpfSolver(native_rib="off").compute_routes(ls, ps, "node-1")
    assert fleet["node-1"].unicast_routes == want.unicast_routes


def test_fleet_chunked_solves():
    """Chunked all-roots solving (chunk < n) must match the per-node
    solver exactly (the memory-bounded fleet path)."""
    adj_dbs, prefix_dbs = topogen.grid(5, 5)
    ls, ps = _state(adj_dbs, prefix_dbs)
    fleet = compute_fleet_ribs(ls, ps, chunk=8)
    per_node = TpuSpfSolver(native_rib="off")
    for node in ("node-0", "node-12", "node-24"):
        want = per_node.compute_routes(ls, ps, node)
        assert fleet[node].unicast_routes == want.unicast_routes, node


def test_fleet_rejects_lfa_solver():
    adj_dbs, prefix_dbs = topogen.ring(4)
    ls, ps = _state(adj_dbs, prefix_dbs)
    with pytest.raises(ValueError):
        compute_fleet_ribs(ls, ps, solver=TpuSpfSolver(enable_lfa=True))


def test_fleet_empty_and_all_unknown_targets():
    adj_dbs, prefix_dbs = topogen.ring(4)
    ls, ps = _state(adj_dbs, prefix_dbs)
    assert compute_fleet_ribs(ls, ps, nodes=[]) == {}
    assert compute_fleet_ribs(ls, ps, nodes=["no-such-node"]) == {}


def test_fleet_mpls_cache_reuse_and_trim():
    """The fleet pass durably raises the MPLS fingerprint cap so a
    SECOND pass reuses the cached entries; trim_caches() reclaims the
    footprint on demand."""
    adj_dbs, prefix_dbs = topogen.grid(4, 4)
    ls, ps = _state(adj_dbs, prefix_dbs)
    solver = TpuSpfSolver(native_rib="off")
    f1 = compute_fleet_ribs(ls, ps, solver=solver)
    n_fp = len(solver._mpls_cache)
    assert n_fp >= len(f1)  # one fingerprint per root retained
    f2 = compute_fleet_ribs(ls, ps, solver=solver)
    # second pass: identical results served from the retained caches
    assert all(
        f1[n].mpls_routes == f2[n].mpls_routes for n in f1
    )
    assert len(solver._mpls_cache) == n_fp  # no thrash between passes
    solver.trim_caches()
    assert len(solver._mpls_cache) <= 8
    assert solver._mpls_fingerprint_cap == 8


def test_fleet_with_mesh_solver_equals_single_device():
    """A mesh-configured solver (sharded split kernel over the virtual
    8-device mesh) must produce the identical fleet of RouteDatabases —
    the combined fleet+mesh path the all-sources production shape
    uses."""
    from openr_tpu.parallel import make_mesh

    adj_dbs, prefix_dbs = topogen.erdos_renyi(
        120, avg_degree=5, seed=17, max_metric=16
    )
    ls, ps = _state(adj_dbs, prefix_dbs)
    base_solver = TpuSpfSolver(native_rib="off")
    want = compute_fleet_ribs(ls, ps, solver=base_solver)
    mesh_solver = TpuSpfSolver(
        native_rib="off",
        mesh=make_mesh(n_sources=4, n_graph=2),
    )
    got = compute_fleet_ribs(ls, ps, solver=mesh_solver)
    # non-vacuousness: the sharded kernel ran, never the single-device
    # fallback
    assert mesh_solver.last_shard_rows
    assert not mesh_solver._mesh_fallback_warned
    assert set(got) == set(want)
    for node in want:
        assert got[node].unicast_routes == want[node].unicast_routes, node
        assert got[node].mpls_routes == want[node].mpls_routes, node


def test_fleet_pass_feeds_no_rib_assembly_stat():
    """`profile.spf:rib_assembly_ms` counts `compute_routes` calls: the
    span with `counters=` sits at that call site, so a fleet pass, which
    assembles a RIB per node through `_assemble_routes`, adds none."""
    from openr_tpu.monitor.counters import Counters

    ls, ps = _state(*topogen.grid(3, 3))
    c = Counters()
    solver = TpuSpfSolver(native_rib="off", counters=c)
    assert len(compute_fleet_ribs(ls, ps, solver=solver)) == 9
    assert "profile.spf:rib_assembly_ms" not in c.stats
    solver.compute_routes(ls, ps, "node-0")
    assert c.stats["profile.spf:rib_assembly_ms"].count == 1
