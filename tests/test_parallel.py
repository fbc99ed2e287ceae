"""Sharded SPF tests on the virtual 8-device CPU mesh (conftest forces
XLA host-platform device count = 8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.decision.linkstate import LinkState
from openr_tpu.decision.oracle import run_spf
from openr_tpu.ops.spf import INF_DIST, pad_batch
from openr_tpu.ops.spf_split import batched_sssp_split, build_split_tables
from openr_tpu.parallel import make_mesh, sharded_sssp_split
from openr_tpu.utils import topogen


def _csr(adj_dbs):
    ls = LinkState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    return ls, ls.to_csr()


def _split_tables(csr):
    """The split tables of `csr` on the device, as the solver uploads
    them: (base_nbr, base_wgt, ov_ids, ov_nbr, ov_wgt), out_nbr, over."""
    t = build_split_tables(
        csr.edge_src, csr.edge_dst, csr.edge_metric, csr.num_nodes
    )
    over = np.zeros(t["vp"], bool)
    over[: csr.num_nodes] = csr.node_overloaded[: csr.num_nodes]
    tables = tuple(
        jnp.asarray(t[k])
        for k in ("base_nbr", "base_wgt", "ov_ids", "ov_nbr", "ov_wgt")
    )
    return tables, jnp.asarray(t["out_nbr"]), jnp.asarray(over)


def _dist(csr, mesh, roots):
    """[vp, len(roots)] from the sharded split kernel, the roots padded
    to their bucket (repeating the first) as `_mesh_fits` expects."""
    padded = np.full(pad_batch(len(roots)), roots[0], dtype=np.int32)
    padded[: len(roots)] = roots
    tables, _out_nbr, over = _split_tables(csr)
    dist = sharded_sssp_split(
        *tables, over, jnp.asarray(padded), mesh,
        has_overloads=bool(csr.node_overloaded.any()),
    )
    return np.asarray(dist)[:, : len(roots)]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@pytest.mark.parametrize("shape", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_sharded_matches_oracle(shape):
    """Every mesh factorization (pure sources, mixed, pure graph-partition
    with the all_gather frontier exchange) must produce identical
    distances."""
    s, g = shape
    adj_dbs, _ = topogen.erdos_renyi(64, avg_degree=4, seed=1, max_metric=50)
    ls, csr = _csr(adj_dbs)
    mesh = make_mesh(n_sources=s, n_graph=g)
    roots = np.arange(64, dtype=np.int32)
    dist = _dist(csr, mesh, roots)
    for root in ("node-0", "node-31", "node-63"):
        res = run_spf(ls, root)
        rid = csr.name_to_id[root]
        for n, i in csr.name_to_id.items():
            want = res.dist.get(n)
            if want is None:
                assert dist[i, rid] >= INF_DIST
            else:
                assert int(dist[i, rid]) == want


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_with_overload():
    adj_dbs, _ = topogen.grid(8, 8)
    from tests.test_spf_kernel import _overload

    for i in (9, 27, 45):
        adj_dbs[i] = _overload(adj_dbs[i])
    ls, csr = _csr(adj_dbs)
    mesh = make_mesh(n_sources=2, n_graph=4)
    roots = np.arange(64, dtype=np.int32)
    dist = _dist(csr, mesh, roots)
    res = run_spf(ls, "node-0")
    for n, i in csr.name_to_id.items():
        want = res.dist.get(n)
        if want is not None:
            assert int(dist[i, 0]) == want, n


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@pytest.mark.parametrize("n_roots", [1, 5, 13])
def test_sharded_padded_uneven_roots(n_roots):
    """Root counts that do NOT divide the sources axis work once padded
    to their bucket and match the oracle."""
    adj_dbs, _ = topogen.erdos_renyi(40, avg_degree=5, seed=3, max_metric=20)
    ls, csr = _csr(adj_dbs)
    mesh = make_mesh(n_sources=4, n_graph=2)
    roots = np.linspace(0, 39, n_roots).astype(np.int32)
    dist = _dist(csr, mesh, roots)
    assert dist.shape[1] == n_roots
    for col, rid in enumerate(roots):
        root = csr.node_names[rid]
        res = run_spf(ls, root)
        for n, i in csr.name_to_id.items():
            want = res.dist.get(n)
            if want is None:
                assert dist[i, col] >= INF_DIST
            else:
                assert int(dist[i, col]) == want, (root, n)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_512_nodes_with_overload():
    """Scale test: 512-node random graph, mixed mesh, overloaded transit
    nodes — sharded distances equal the oracle from spot-check roots."""
    adj_dbs, _ = topogen.erdos_renyi(512, avg_degree=6, seed=9, max_metric=40)
    from tests.test_spf_kernel import _overload

    for i in (50, 200, 350):
        adj_dbs[i] = _overload(adj_dbs[i])
    ls, csr = _csr(adj_dbs)
    mesh = make_mesh(n_sources=4, n_graph=2)
    roots = np.arange(512, dtype=np.int32)
    dist = _dist(csr, mesh, roots)
    for root in ("node-0", "node-255", "node-350", "node-511"):
        res = run_spf(ls, root)
        rid = csr.name_to_id[root]
        for n, i in csr.name_to_id.items():
            want = res.dist.get(n)
            if want is None:
                assert dist[i, rid] >= INF_DIST, (root, n)
            else:
                assert int(dist[i, rid]) == want, (root, n)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_all_sources_chunked_matches_sharded():
    """All sources solved in chunks on one device agree with the sharded
    solve column-for-column."""
    adj_dbs, _ = topogen.erdos_renyi(96, avg_degree=5, seed=5, max_metric=30)
    ls, csr = _csr(adj_dbs)
    tables, out_nbr, over = _split_tables(csr)
    full = np.concatenate(
        [
            np.asarray(
                batched_sssp_split(
                    *tables, out_nbr, over,
                    jnp.arange(start, start + 32, dtype=jnp.int32),
                    has_overloads=False,
                )
            )
            for start in range(0, 96, 32)  # several chunks
        ],
        axis=1,
    )
    mesh = make_mesh(n_sources=8, n_graph=1)
    roots = np.arange(96, dtype=np.int32)
    dist = _dist(csr, mesh, roots)
    np.testing.assert_array_equal(full[:96], dist[:96])


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@pytest.mark.parametrize("shape", [(4, 2), (2, 4), (1, 8)])
def test_sharded_split_kernel_matches_single_device(shape):
    """The flagship v3 split kernel under sources x graph sharding must
    equal the single-device split kernel (and transitively the oracle),
    including with overloaded nodes."""
    es, ed, em, vp, nn, _e = topogen.erdos_renyi_csr(
        700, avg_degree=6, seed=21, max_metric=32
    )
    t = build_split_tables(es, ed, em, nn)
    vps = t["vp"]
    over = np.zeros(vps, bool)
    over[[5, 17, 40]] = True
    rng = np.random.default_rng(3)
    roots = rng.integers(0, nn, 16).astype(np.int32)
    roots[0] = 5  # overloaded root: exemption path
    s, g = shape
    mesh = make_mesh(n_sources=s, n_graph=g, devices=jax.devices()[:8])
    args = (
        jnp.asarray(t["base_nbr"]), jnp.asarray(t["base_wgt"]),
        jnp.asarray(t["ov_ids"]), jnp.asarray(t["ov_nbr"]),
        jnp.asarray(t["ov_wgt"]),
    )
    got = np.asarray(
        sharded_sssp_split(
            *args, jnp.asarray(over), jnp.asarray(roots), mesh,
            has_overloads=True,
        )
    )
    ref = np.asarray(
        batched_sssp_split(
            *args, jnp.asarray(t["out_nbr"]), jnp.asarray(over),
            jnp.asarray(roots), has_overloads=True,
        )
    )
    np.testing.assert_array_equal(got[:nn], ref[:nn])


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_mesh_configured_solver_matches_single_device():
    """A TpuSpfSolver given a mesh routes batched solves through the
    sharded split kernel; distances, fleet RIBs, and the single-root
    production rebuild must all equal the single-device solver's."""
    from openr_tpu.decision.fleet import compute_fleet_ribs
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.utils.topogen import erdos_renyi_lsdb

    ls, ps, csr = erdos_renyi_lsdb(300, avg_degree=5, seed=9, max_metric=16)
    mesh = make_mesh(n_sources=4, n_graph=2, devices=jax.devices()[:8])
    meshed = TpuSpfSolver(native_rib="off", mesh=mesh)
    plain = TpuSpfSolver(native_rib="off")

    roots = np.arange(64, dtype=np.int32) % csr.num_nodes
    np.testing.assert_array_equal(
        np.asarray(meshed._solve_dist(csr, roots)),
        np.asarray(plain._solve_dist(csr, roots)),
    )
    # production single-root rebuild: identical RIBs (and the meshed
    # solver's solve() stays on the fused single-device path)
    assert meshed.compute_routes(ls, ps, "node-0") == plain.compute_routes(
        ls, ps, "node-0"
    )
    # whole-fleet shape through the sharded kernel
    some = [f"node-{i}" for i in range(0, 30, 3)]
    fa = compute_fleet_ribs(ls, ps, nodes=some, solver=meshed)
    fb = compute_fleet_ribs(ls, ps, nodes=some, solver=plain)
    assert fa == fb and len(fa) == len(some)
