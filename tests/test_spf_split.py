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

from openr_tpu.common.constants import DIST_INF
from openr_tpu.ops.spf import (
    batched_sssp_dense,
    build_dense_tables,
    first_hop_matrix,
    lfa_matrix,
    pad_batch,
)
from openr_tpu.ops.spf_split import (
    _small_frontier,
    batched_sssp_split,
    batched_sssp_split_warm_rib,
    build_split_tables,
    pick_base_width,
    rib_buffer_trailer,
    tight_nodes,
)
from openr_tpu.utils import topogen


def _edge_arrays(edges, vp, ep):
    """(src, dst, metric) triples as the dst-sorted arrays the table
    builders read, padded to `ep` slots with dead INF edges."""
    pad = ep - len(edges)
    es = np.array([e[0] for e in edges] + [0] * pad, np.int32)
    ed = np.array([e[1] for e in edges] + [vp - 1] * pad, np.int32)
    em = np.array([e[2] for e in edges] + [DIST_INF] * pad, np.int32)
    order = np.argsort(ed, kind="stable")
    return es[order], ed[order], em[order]


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
    vp = 128
    es, ed, em = _edge_arrays(edges, vp, 512)
    roots = np.zeros(8, dtype=np.int32)
    ref, got = _solve_both(
        es, ed, em, vp, n, roots,
        # threshold bigger than cap: phase 1 exits immediately with a
        # ~99-row changed set that cannot fit the 32-slot tail
        tail_threshold=n, tail_cap=32, tail_rounds_cap=64,
    )
    np.testing.assert_array_equal(ref, got)


# ---- a tail round sized by its live counts (`_small_frontier`) ------------
# A ring of RING nodes (out-degree 2) with two hubs hung on it: HUB_A on 40
# ring nodes, HUB_B on 300. At tail_cap 256 the small expansion has 8
# frontier slots and a relaxed chunk 4 rows: k ring seeds expand to 3k rows,
# HUB_A to 41 (the last chunk partly filled), HUB_B to 301 (past tail_cap).
RING, HUB_A, HUB_B = 1000, 1000, 1001
TIER_CAP = 256


def _ring_with_hubs(overloads):
    """(es, ed, em, vp, n, node_overloaded, roots)"""
    rng = np.random.default_rng(31)
    edges = []
    for i in range(RING):
        m = int(rng.integers(1, 6))
        edges += [(i, (i + 1) % RING, m), ((i + 1) % RING, i, m)]
    for hub, spokes in ((HUB_A, range(500, 540)), (HUB_B, range(600, 900))):
        for i in spokes:
            edges += [(hub, i, 7), (i, hub, 7)]
    n = RING + 2
    vp = tight_nodes(n)
    over = np.zeros(vp, bool)
    if overloads:
        over[[3, 255, 520, 700]] = True
    roots = np.array([0, 450, 950, 3, 605, 1, 2, 4], np.int32)
    return *_edge_arrays(edges, vp, pad_batch(len(edges))), vp, n, over, roots


def _ring_seeds(k):
    """k ring nodes of out-degree 2, no two adjacent, none a root."""
    return [10 + 10 * i for i in range(k)]


def test_small_frontier_of_the_capacities_the_cases_use():
    assert _small_frontier(8192) == 256
    assert _small_frontier(TIER_CAP) == 8
    assert _small_frontier(255) is None and _small_frontier(32) is None


@pytest.mark.parametrize(
    "seeds,tail_cap,overloads,rounds,small_rounds,spilled",
    [
        # frontier of F_s - 1, F_s, F_s + 1 live ids: the last is past
        # the small capacity by one id, both its rounds expand at tail_cap
        pytest.param(_ring_seeds(7), TIER_CAP, False, 2, 2, 0, id="F_s-1"),
        pytest.param(_ring_seeds(8), TIER_CAP, False, 2, 2, 0, id="F_s"),
        pytest.param(_ring_seeds(9), TIER_CAP, False, 2, 0, 0, id="F_s+1"),
        # one live id and 41 rows: the last chunk is partly filled
        pytest.param([HUB_A], TIER_CAP, False, 2, 2, 0, id="hub-41-rows"),
        # one live id whose expansion passes tail_cap: the only spill
        pytest.param([HUB_B], TIER_CAP, False, 1, 1, 1, id="over-tail_cap"),
        pytest.param([HUB_B], 512, False, 2, 2, 0, id="hub-301-rows"),
        pytest.param(
            _ring_seeds(20) + [HUB_B], 512, False, 2, 0, 0,
            id="full-expansion-361-rows",
        ),
        # a tail_cap too small for two expansion capacities
        pytest.param(_ring_seeds(7), 32, False, 2, 0, 0, id="one-capacity"),
        pytest.param(_ring_seeds(8), TIER_CAP, True, 2, 2, 0, id="overloads"),
        pytest.param(
            _ring_seeds(5) + [HUB_A], TIER_CAP, True, 2, 2, 0,
            id="overloads-hub",
        ),
    ],
)
def test_tail_sizes_warm_entry(
    seeds, tail_cap, overloads, rounds, small_rounds, spilled
):
    """The warm entry from seeds of each size: the true distances with
    the seeds' rows at INF are upper bounds, so the kernel must land on
    `batched_sssp_dense`'s distances at whichever sizes each round ran,
    and the trailer says which expansion it took."""
    es, ed, em, vp, n, over, roots = _ring_with_hubs(overloads)
    nbr, wgt = build_dense_tables(es, ed, em, vp)
    ref = np.asarray(batched_sssp_dense(
        jnp.asarray(nbr), jnp.asarray(wgt), jnp.asarray(over),
        jnp.asarray(roots), has_overloads=overloads,
    ))
    t = build_split_tables(es, ed, em, n)
    assert t["vp"] == vp
    dist0 = ref.copy()
    dist0[seeds] = DIST_INF
    seed_mask = np.zeros(t["vp"], bool)
    seed_mask[seeds] = True
    b = len(roots)
    dist, packed = batched_sssp_split_warm_rib(
        jnp.asarray(t["base_nbr"]), jnp.asarray(t["base_wgt"]),
        jnp.asarray(t["ov_ids"]), jnp.asarray(t["ov_nbr"]),
        jnp.asarray(t["ov_wgt"]), jnp.asarray(t["out_nbr"]),
        jnp.asarray(over), jnp.asarray(roots),
        jnp.ones(b - 1, jnp.int32), jnp.asarray(roots[1:]),
        jnp.zeros(b - 1, bool), jnp.asarray(dist0), jnp.asarray(seed_mask),
        has_overloads=overloads, tail_cap=tail_cap,
    )
    np.testing.assert_array_equal(np.asarray(dist), ref)
    got = rib_buffer_trailer(np.asarray(packed))
    assert got["dense_sweeps"] == 0
    assert got["spilled"] == spilled
    assert (got["net_sweeps"] >= 1) == bool(spilled)
    assert got["tail_rounds"] == rounds
    assert got["tail_small_rounds"] == small_rounds


@pytest.mark.parametrize("tail_cap", [32, TIER_CAP, 8192])
@pytest.mark.parametrize("overloads", [False, True])
def test_tail_sizes_cold_entry(tail_cap, overloads):
    """The cold entry straight into the tail (one dense sweep): a wave
    of two or three rows walks the ring and meets the hubs on its way,
    so one solve takes rounds of either expansion capacity and of one or
    several chunks (or spills at 32)."""
    es, ed, em, vp, n, over, roots = _ring_with_hubs(overloads)
    ref, got = _solve_both(
        es, ed, em, vp, n, roots, over=over,
        tail_threshold=n, tail_cap=tail_cap, tail_rounds_cap=2048,
    )
    np.testing.assert_array_equal(ref, got)


def test_split_disconnected_and_line():
    # line graph: worst-case hop diameter exercises many sweeps
    n = 64
    edges = []
    for i in range(n - 1):
        edges.append((i, i + 1, 3))
        edges.append((i + 1, i, 3))
    vp = 128
    es, ed, em = _edge_arrays(edges, vp, 256)
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
