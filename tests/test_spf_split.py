"""Tests for the v3 split-table SPF kernel (ops/spf_split.py).

Mirrors the reference's Decision test style (golden distances on
synthetic graphs; reference: openr/decision/tests/DecisionTest.cpp †):
the v3 kernel must produce byte-identical distances to the plain
Bellman-Ford over the full-width tables (`batched_sssp_dense`, the
reference) on every topology class, including overloads, and through
its tail/spill phases.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.ops.spf import (
    batched_sssp_dense,
    build_dense_tables,
    first_hop_matrix,
    lfa_matrix,
    pad_batch,
)
from openr_tpu.ops.spf_split import (
    batched_sssp_split,
    build_split_tables,
    pick_base_width,
    tight_nodes,
)
from openr_tpu.utils import topogen


def _solve_both(es, ed, em, vp, n, roots, over=None, **tail_kw):
    nbr, wgt = build_dense_tables(es, ed, em, vp)
    if over is None:
        over = np.zeros(vp, bool)
    has_over = bool(over.any())
    ref = np.asarray(
        batched_sssp_dense(
            jnp.asarray(nbr), jnp.asarray(wgt), jnp.asarray(over),
            jnp.asarray(roots), has_overloads=has_over,
        )
    )
    t = build_split_tables(es, ed, em, n)
    vp2 = t["vp"]
    over2 = np.zeros(vp2, bool)
    m = min(vp, vp2)
    over2[:m] = over[:m]
    got = np.asarray(
        batched_sssp_split(
            jnp.asarray(t["base_nbr"]), jnp.asarray(t["base_wgt"]),
            jnp.asarray(t["ov_ids"]), jnp.asarray(t["ov_nbr"]),
            jnp.asarray(t["ov_wgt"]), jnp.asarray(t["out_nbr"]),
            jnp.asarray(over2), jnp.asarray(roots),
            has_overloads=has_over, **tail_kw,
        )
    )
    lim = min(n, vp, vp2)
    return ref[:lim], got[:lim]


@pytest.mark.parametrize(
    "n,deg,mw,seed",
    [
        (200, 4, 8, 3), (1000, 8, 64, 3), (2000, 16, 16, 3),
        (256, 8, 16, 0), (256, 8, 16, 1),
        (512, 16, 8, 0), (512, 16, 8, 1),
    ],
)
def test_split_matches_dense_er(n, deg, mw, seed):
    es, ed, em, vp, nn, _e = topogen.erdos_renyi_csr(
        n, avg_degree=deg, seed=seed, max_metric=mw
    )
    roots = np.arange(pad_batch(8), dtype=np.int32) % nn
    ref, got = _solve_both(es, ed, em, vp, nn, roots)
    np.testing.assert_array_equal(ref, got)


# 9000 → vp=9216 ≥ GS_MIN_VP: the DEFAULT picker runs chunked sweeps,
# so the dense-equality assertion covers the production GS path (the
# explicit-override coverage is test_split_gs_chunk_counts_all_equal)
@pytest.mark.parametrize("n", [256, 800, 9000])
def test_split_matches_dense_overloads(n):
    es, ed, em, vp, nn, _e = topogen.erdos_renyi_csr(
        n, avg_degree=6, seed=5, max_metric=32
    )
    rng = np.random.default_rng(7)
    over = np.zeros(vp, bool)
    over[rng.integers(0, nn, 40)] = True
    roots = rng.integers(0, nn, pad_batch(10)).astype(np.int32)
    # include an overloaded root (the exemption path)
    roots[0] = np.nonzero(over)[0][0]
    ref, got = _solve_both(es, ed, em, vp, nn, roots, over=over)
    np.testing.assert_array_equal(ref, got)


def test_split_tail_and_spill_paths():
    """Tiny tail capacity forces both the spill path (dense fallback)
    and, with a larger cap, the pure-tail path — results identical."""
    es, ed, em, vp, nn, _e = topogen.erdos_renyi_csr(
        600, avg_degree=5, seed=11, max_metric=64
    )
    roots = np.zeros(pad_batch(4), dtype=np.int32)
    ref, got_spill = _solve_both(
        es, ed, em, vp, nn, roots,
        tail_threshold=nn, tail_cap=32, tail_rounds_cap=4,
    )
    np.testing.assert_array_equal(ref, got_spill)
    ref2, got_tail = _solve_both(
        es, ed, em, vp, nn, roots,
        tail_threshold=nn, tail_cap=2048, tail_rounds_cap=512,
    )
    np.testing.assert_array_equal(ref2, got_tail)


def test_split_entry_spill_exceeding_tail_cap():
    """Phase-1 can exit with MORE changed rows than tail_cap whenever
    tail_threshold > tail_cap; the entry spill must route to the dense
    safety net instead of truncating the frontier (review finding)."""
    # star + chain: the hub's first sweep changes ~100 rows at once
    n = 120
    edges = []
    for i in range(1, 100):
        edges += [(0, i, 1 + i % 7), (i, 0, 1 + i % 7)]
    for i in range(100, n):
        edges += [(i - 1, i, 3), (i, i - 1, 3)]
    src = np.array([e[0] for e in edges], np.int32)
    dst = np.array([e[1] for e in edges], np.int32)
    met = np.array([e[2] for e in edges], np.int32)
    from openr_tpu.common.constants import DIST_INF

    vp = 128
    ep = 512
    pad = ep - len(src)
    es = np.concatenate([src, np.zeros(pad, np.int32)])
    ed = np.concatenate([dst, np.full(pad, vp - 1, np.int32)])
    em = np.concatenate([met, np.full(pad, DIST_INF, np.int32)])
    order = np.argsort(ed, kind="stable")
    es, ed, em = es[order], ed[order], em[order]
    roots = np.zeros(8, dtype=np.int32)
    ref, got = _solve_both(
        es, ed, em, vp, n, roots,
        # threshold bigger than cap: phase 1 exits immediately with a
        # ~99-row changed set that cannot fit the 32-slot tail
        tail_threshold=n, tail_cap=32, tail_rounds_cap=64,
    )
    np.testing.assert_array_equal(ref, got)


def test_split_disconnected_and_line():
    # line graph: worst-case hop diameter exercises many sweeps
    n = 64
    edges = []
    for i in range(n - 1):
        edges.append((i, i + 1, 3))
        edges.append((i + 1, i, 3))
    src = np.array([e[0] for e in edges], dtype=np.int32)
    dst = np.array([e[1] for e in edges], dtype=np.int32)
    met = np.array([e[2] for e in edges], dtype=np.int32)
    order = np.argsort(dst, kind="stable")
    src, dst, met = src[order], dst[order], met[order]
    vp = 128
    from openr_tpu.common.constants import DIST_INF

    pad = 256 - len(src)
    es = np.concatenate([src, np.zeros(pad, np.int32)])
    ed = np.concatenate([dst, np.full(pad, vp - 1, np.int32)])
    em = np.concatenate([met, np.full(pad, DIST_INF, np.int32)])
    order = np.argsort(ed, kind="stable")
    es, ed, em = es[order], ed[order], em[order]
    roots = np.zeros(8, dtype=np.int32)
    ref, got = _solve_both(es, ed, em, vp, n, roots)
    np.testing.assert_array_equal(ref, got)
    # node n-1 unreachable from nothing — all reachable here; check value
    assert got[n - 1, 0] == 3 * (n - 1)


def test_tight_nodes_and_width_picker():
    assert tight_nodes(100_000) == 106_496  # 13 * 2^13 (1/8-octave grid)
    assert tight_nodes(512) == 1024  # strictly greater => dead slot exists
    assert tight_nodes(511) == 512
    # Poisson(22) (the 100k ER bench profile) -> W=32: base covers
    # ~98% of rows, the padded overflow table stays tiny
    indeg = np.random.default_rng(0).poisson(22, 100_000)
    assert pick_base_width(indeg) == 32
    # one mega-hub: W small + overflow, never W=4096
    indeg = np.full(1000, 4)
    indeg[0] = 4096
    assert pick_base_width(indeg) <= 8


def test_fused_rib_path_matches_dense_and_lazy_dist():
    """batched_sssp_split_rib (fused solve + packed d_root/fh/lfa) must
    produce byte-identical results to the reference solve with the
    first-hop / LFA identities applied to its distances, and _LazyDist
    must serve every spelling of the root column without a full
    materialization."""
    from openr_tpu.decision.oracle import compute_routes as oracle_routes
    from openr_tpu.decision.spf_backend import TpuSpfSolver, _LazyDist

    ls, ps, csr = topogen.erdos_renyi_lsdb(
        220, avg_degree=6, seed=7, max_metric=64
    )
    n = csr.num_nodes
    my_id = csr.name_to_id["node-0"]
    nbr, wgt = csr.dense_tables()
    for lfa in (False, True):
        a = TpuSpfSolver(native_rib="off", enable_lfa=lfa)  # fused split
        sa = a.solve(ls, "node-0")
        nbr_ids = np.array(sa[3], np.int32)
        k = len(nbr_ids)
        over = jnp.asarray(csr.node_overloaded)
        nbr_over = jnp.asarray(csr.node_overloaded[nbr_ids])
        ref = batched_sssp_dense(
            jnp.asarray(nbr), jnp.asarray(wgt), over,
            jnp.asarray(np.array([my_id, *nbr_ids], np.int32)),
            has_overloads=bool(csr.node_overloaded.any()),
        )
        ref_np = np.asarray(ref)
        assert isinstance(sa[1], _LazyDist)
        # root column fast path: several spellings, no materialization
        assert sa[1]._np is None
        np.testing.assert_array_equal(sa[1][:, 0][:n], ref_np[:n, 0])
        np.testing.assert_array_equal(sa[1][:n, 0], ref_np[:n, 0])
        np.testing.assert_array_equal(
            sa[1][:, np.int32(0)][:n], ref_np[:n, 0]
        )
        assert sa[1]._np is None, "root-column reads must not transfer"
        # full materialization agrees (columns past 1 + k are padding)
        np.testing.assert_array_equal(
            np.asarray(sa[1])[:n, : 1 + k], ref_np[:n]
        )
        want_fh = np.asarray(first_hop_matrix(
            ref, jnp.asarray(a._nbr_metrics(csr, my_id, sa[3])),
            jnp.asarray(nbr_ids), nbr_over,
        ))
        np.testing.assert_array_equal(sa[2][:k, :n], want_fh[:, :n])
        assert not sa[2][k:].any()
        if lfa:
            want_lfa = np.asarray(lfa_matrix(
                ref, jnp.int32(my_id), jnp.asarray(nbr_ids), nbr_over,
            ))
            np.testing.assert_array_equal(sa[4][:k, :n], want_lfa[:, :n])
            assert not sa[4][k:].any()
        got = a.compute_routes(ls, ps, "node-0")
        want = oracle_routes(ls, ps, "node-0", enable_lfa=lfa)
        assert got.unicast_routes == want.unicast_routes
        assert got.mpls_routes == want.mpls_routes


def test_uni_cache_not_fooled_by_parallel_prefix_states():
    """Two independent PrefixState instances can reach the same _rev with
    different prefix contents; a shared solver's cross-rebuild unicast
    cache must not serve one state's RibEntrys for the other (lineage id
    in the solver_view gen)."""
    from openr_tpu.decision.linkstate import PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.types.topology import PrefixDatabase, PrefixEntry

    ls, ps_a, csr = topogen.erdos_renyi_lsdb(
        64, avg_degree=4, seed=11, max_metric=16
    )

    def mk_ps(tag):
        ps = PrefixState()
        for i, name in enumerate(csr.node_names):
            ps.update_prefix_db(
                PrefixDatabase(
                    this_node_name=name,
                    prefix_entries=(
                        PrefixEntry(prefix=f"10.{tag}.{i}.0/24"),
                    ),
                )
            )
        return ps

    a, b = mk_ps(1), mk_ps(2)
    assert a._rev == b._rev  # the collision the lineage id must break
    solver = TpuSpfSolver(native_rib="off")
    ra = solver.compute_routes(ls, a, "node-0")
    rb = solver.compute_routes(ls, b, "node-0")
    assert all(str(p).startswith("10.1.") for p in ra.unicast_routes)
    assert all(str(p).startswith("10.2.") for p in rb.unicast_routes)
    assert len(ra.unicast_routes) == len(rb.unicast_routes) > 0


def test_pick_gs_chunks_never_silently_disables():
    """Round-3 verdict weak 5: the old rule (vp % 2048 == 0) lost GS
    chunking for any padding not a multiple of 2048. The new picker
    must chunk EVERY large tight_nodes() padding and stay off only for
    small graphs (where chunk overhead beats the sweep-count win)."""
    from openr_tpu.ops.spf_split import GS_CHUNKS, GS_MIN_VP, pick_gs_chunks

    # every tight padding a real graph can produce, including the odd
    # multiples of 512 the old rule silently dropped (e.g. 2560, 99840)
    for n in [8191, 9000, 99_000, 100_000, 2559, 50_001]:
        vp = tight_nodes(n)
        gs = pick_gs_chunks(vp)
        if vp >= GS_MIN_VP:
            assert gs > 1, (n, vp, gs)
            assert vp % gs == 0 and (vp // gs) % 8 == 0
            assert gs <= GS_CHUNKS
        else:
            assert gs == 1
    assert pick_gs_chunks(512) == 1  # tiny graph: chunking off


@pytest.mark.parametrize("gs", [1, 2, 3, 4])
def test_split_gs_chunk_counts_all_equal(gs):
    """Any Gauss-Seidel block count reaches the same fixpoint (relax
    order is irrelevant for the monotone min system) — pin it for every
    count the picker can emit, via the explicit override."""
    es, ed, em, vp, nn, _e = topogen.erdos_renyi_csr(
        1500, avg_degree=6, seed=13, max_metric=32
    )
    roots = np.arange(pad_batch(6), dtype=np.int32) % nn
    ref, got = _solve_both(es, ed, em, vp, nn, roots, gs_chunks=gs)
    np.testing.assert_array_equal(ref, got)


def test_uniform_metric_detection_and_convergence():
    """build_split_tables flags the hop-count regime (Open/R's default
    metric 1); the kernel needs no separate path — uniform metrics
    converge in ~diameter dense sweeps automatically — but distances
    must equal the dense kernel's and scale by the uniform metric."""
    es, ed, em, vp, nn, _e = topogen.erdos_renyi_csr(
        1200, avg_degree=8, seed=17, max_metric=1
    )
    assert (em[em < (1 << 30)] == 1).all()
    t = build_split_tables(es, ed, em, nn)
    assert t["uniform_metric"] == 1

    roots = np.arange(pad_batch(4), dtype=np.int32) % nn
    ref, got = _solve_both(es, ed, em, vp, nn, roots)
    np.testing.assert_array_equal(ref, got)

    # metric 7 everywhere: still uniform, distances = 7 × hop count
    em7 = np.where(em < (1 << 30), em * 7, em)
    t7 = build_split_tables(es, ed, em7, nn)
    assert t7["uniform_metric"] == 7
    ref7, got7 = _solve_both(es, ed, em7, vp, nn, roots)
    np.testing.assert_array_equal(ref7, got7)
    lim = min(len(ref), len(ref7))
    inf = 1 << 30
    fin = ref[:lim] < inf
    np.testing.assert_array_equal(
        ref7[:lim][fin], ref[:lim][fin] * 7
    )

    # mixed metrics: detection must stay off
    em_mixed = em.copy()
    em_mixed[np.nonzero(em_mixed < inf)[0][0]] = 3
    assert build_split_tables(es, ed, em_mixed, nn)["uniform_metric"] == 0


def test_backend_kernel_stats_and_patch_clears_uniform():
    """The solver surfaces gs/uniform regime counters, and a churn
    patch that breaks metric uniformity clears the dset marker."""
    from openr_tpu.decision.linkstate import LinkState
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.types.topology import Adjacency, AdjacencyDatabase

    def adj(other, ifn, metric):
        return Adjacency(
            other_node_name=other, if_name=ifn,
            other_if_name=f"to-{ifn}", metric=metric,
        )

    n = 8
    ls = LinkState("0")
    for i in range(n):
        ls.update_adjacency_db(AdjacencyDatabase(
            this_node_name=f"n{i}",
            adjacencies=(
                adj(f"n{(i - 1) % n}", f"if{i}a", 10),
                adj(f"n{(i + 1) % n}", f"if{i}b", 10),
            ),
        ))
    solver = TpuSpfSolver(native_rib="off")
    csr = ls.to_csr()
    dev = solver._device_arrays(csr, "split")
    assert dev["uniform_metric"] == 10
    roots = np.zeros(pad_batch(2), np.int32)
    solver._solve_dist(csr, roots)
    assert solver.spf_kernel_stats["uniform_metric"] >= 1
    assert (
        solver.spf_kernel_stats["gs_active"]
        + solver.spf_kernel_stats["gs_disabled"]
    ) >= 1

    # break uniformity via a metric-only change (journal patch path)
    assert ls.update_adjacency_db(AdjacencyDatabase(
        this_node_name="n3",
        adjacencies=(adj("n2", "if3a", 10), adj("n4", "if3b", 77)),
    ))
    csr2 = ls.to_csr()
    assert csr2.patches, "metric change must take the patch path"
    dev2 = solver._device_arrays(csr2, "split")
    assert dev2["uniform_metric"] == 0
