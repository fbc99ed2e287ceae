"""Device KSP kernel vs host oracle equivalence.

ops/ksp.ksp_edge_disjoint_dense must produce byte-identical
(cost, path) lists to decision/ksp.k_edge_disjoint_paths — same
deterministic predecessor rule, same both-direction link bans — on
random graphs with asymmetric metrics, overloaded nodes, unreachable
destinations, and k up to 16 (reference analogue: DecisionTest KSP2
cases †, generalized to BASELINE config 4's k=16)."""

import numpy as np
import pytest

from openr_tpu.decision.ksp import k_edge_disjoint_paths
from openr_tpu.ops.ksp import (
    build_ksp_blocked,
    ksp_edge_disjoint_dense,
    paths_to_host,
)
from openr_tpu.ops.spf import INF_DIST, build_dense_tables, pad_batch


def pad_dests(dests: np.ndarray, root_id: int) -> np.ndarray:
    """The production dest-batch discipline (spf_backend._ksp_batch):
    pad to a power-of-two bucket with dest==root dead jobs, so every
    batch size in a bucket reuses one compiled kernel variant (orlint
    OR010). Padded jobs yield cost=INF / empty paths by construction."""
    b = pad_batch(len(dests))
    out = np.full(b, root_id, dtype=np.int32)
    out[: len(dests)] = dests
    return out


def dense_of(names, adj):
    """Dense in-neighbor tables of an oracle-form adjacency dict."""
    idx = {nm: i for i, nm in enumerate(names)}
    edges = sorted(
        ((idx[u], idx[v], w) for u, nbrs in adj.items() for v, w in nbrs.items()),
        key=lambda e: (e[1], e[0]),
    )
    return build_dense_tables(
        np.array([e[0] for e in edges], np.int32),
        np.array([e[1] for e in edges], np.int32),
        np.array([e[2] for e in edges], np.int32),
        len(names),
    )


def random_graph(rng, n, p=0.25, max_metric=10):
    """Random symmetric-connectivity digraph with asymmetric metrics.

    Returns (adj dict for the oracle, dense nbr/wgt tables, names)."""
    names = [f"n{i:03d}" for i in range(n)]
    adj = {nm: {} for nm in names}
    for i in range(n):
        for j in range(i + 1, n):
            if rng.random() < p:
                adj[names[i]][names[j]] = int(rng.integers(1, max_metric + 1))
                adj[names[j]][names[i]] = int(rng.integers(1, max_metric + 1))
    nbr, wgt = dense_of(names, adj)
    return adj, nbr, wgt, names


@pytest.mark.parametrize("k", [2, 4, 16])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ksp_kernel_matches_oracle(k, seed):
    rng = np.random.default_rng(seed)
    n = 24
    adj, nbr, wgt, names = random_graph(rng, n)
    overloaded_ids = sorted(rng.choice(n, size=2, replace=False))
    overloaded = {names[i] for i in overloaded_ids}
    over_mask = np.zeros(n, dtype=bool)
    over_mask[overloaded_ids] = True

    root_id = 0
    dests = np.array(
        sorted(rng.choice(np.arange(1, n), size=8, replace=False)),
        dtype=np.int32,
    )
    blocked = build_ksp_blocked(nbr, over_mask, root_id)
    costs, paths, hops = ksp_edge_disjoint_dense(
        nbr, wgt, blocked, np.int32(root_id), pad_dests(dests, root_id),
        k=k, max_hops=n - 1,
    )
    costs, paths, hops = np.asarray(costs), np.asarray(paths), np.asarray(hops)

    for b, dest_id in enumerate(dests):
        want = k_edge_disjoint_paths(
            adj, names[root_id], [names[dest_id]], overloaded, k=k
        )
        got = paths_to_host(costs, paths, hops, names, b)
        assert got == want, (
            f"k={k} seed={seed} dest={names[dest_id]}:\n"
            f"device={got}\noracle={want}"
        )


def test_ksp_kernel_root_and_unreachable():
    """dest == root and unreachable dest both yield zero paths."""
    rng = np.random.default_rng(7)
    # two disconnected components: 0..5 and 6..11
    names = [f"n{i:03d}" for i in range(12)]
    adj = {nm: {} for nm in names}
    for base in (0, 6):
        for i in range(base, base + 5):
            adj[names[i]][names[i + 1]] = 1
            adj[names[i + 1]][names[i]] = 1
    nbr, wgt = dense_of(names, adj)
    blocked = build_ksp_blocked(nbr, np.zeros(12, bool), 0)
    dests = np.array([0, 8], dtype=np.int32)  # root itself; other component
    costs, paths, hops = ksp_edge_disjoint_dense(
        nbr, wgt, blocked, np.int32(0), dests, k=4, max_hops=11
    )
    costs, paths, hops = np.asarray(costs), np.asarray(paths), np.asarray(hops)
    assert (costs >= int(INF_DIST)).all()
    assert paths_to_host(costs, paths, hops, names, 0) == []
    assert paths_to_host(costs, paths, hops, names, 1) == []


def test_ksp_kernel_parallel_capacity_line():
    """A 4-node ladder: exactly 2 edge-disjoint paths exist; rounds 3+
    must report no path (bans exhausted the cut)."""
    # 0-1-3 and 0-2-3
    names = ["a", "b", "c", "d"]
    adj = {
        "a": {"b": 1, "c": 1},
        "b": {"a": 1, "d": 1},
        "c": {"a": 1, "d": 1},
        "d": {"b": 1, "c": 1},
    }
    nbr, wgt = dense_of(names, adj)
    blocked = build_ksp_blocked(nbr, np.zeros(4, bool), 0)
    costs, paths, hops = ksp_edge_disjoint_dense(
        nbr, wgt, blocked, np.int32(0), np.array([3], np.int32),
        k=4, max_hops=3,
    )
    got = paths_to_host(
        np.asarray(costs), np.asarray(paths), np.asarray(hops), names, 0
    )
    assert got == [(2, ["a", "b", "d"]), (2, ["a", "c", "d"])]
    want = k_edge_disjoint_paths(adj, "a", ["d"], set(), k=4)
    assert got == want


def ring_adj(n):
    names = [f"n{i:03d}" for i in range(n)]
    adj = {nm: {} for nm in names}
    for i in range(n):
        adj[names[i]][names[(i + 1) % n]] = 1
        adj[names[(i + 1) % n]][names[i]] = 1
    return names, adj


# every decode case runs the kernel at these: one program a batch size,
# and padding behind every path (the widest graph has 24 nodes)
DECODE_K = 4
DECODE_MAX_HOPS = 23


def decode_case(case):
    """(names, adj, dests as dispatched) of a batch the decode has to
    get right; the root is node 0."""
    if case == "ring":  # two ways round to every node, then failed rounds
        names, adj = ring_adj(8)
        return names, adj, np.arange(1, 8, dtype=np.int32)
    if case == "failed_round":  # the ladder: rounds 3 and 4 find nothing
        names = ["a", "b", "c", "d"]
        adj = {"a": {"b": 1, "c": 1}, "b": {"a": 1, "d": 1},
               "c": {"a": 1, "d": 1}, "d": {"b": 1, "c": 1}}
        return names, adj, np.array([3], np.int32)
    if case == "padded_job":  # 3 jobs in a bucket of 4: dest == root
        names, adj = ring_adj(6)
        return names, adj, pad_dests(np.array([2, 3, 5], np.int32), 0)
    if case == "one_hop":  # the root's two neighbours: a path of one hop
        names, adj = ring_adj(5)
        return names, adj, np.array([1, 4], np.int32)
    assert case == "random"
    rng = np.random.default_rng(5)
    adj, _nbr, _wgt, names = random_graph(rng, 24)
    return names, adj, pad_dests(np.arange(1, 24, dtype=np.int32), 0)


DECODE_CASES = ("ring", "failed_round", "padded_job", "one_hop", "random")


def run_decode_case(case):
    names, adj, dests = decode_case(case)
    nbr, wgt = dense_of(names, adj)
    blocked = build_ksp_blocked(nbr, np.zeros(len(names), bool), 0)
    costs, paths, hops = ksp_edge_disjoint_dense(
        nbr, wgt, blocked, np.int32(0), dests,
        k=DECODE_K, max_hops=DECODE_MAX_HOPS,
    )
    return names, dests, np.asarray(costs), np.asarray(paths), np.asarray(hops)


def paths_to_host_per_slot(costs, paths, node_names, job):
    """The decode as it was before it read `hops`: every slot of a path,
    filtered one numpy scalar at a time. Kept as the reference form."""
    out = []
    for i in range(costs.shape[0]):
        c = int(costs[i, job])
        if c >= int(INF_DIST):
            continue
        ids = [int(x) for x in paths[i, job] if x >= 0]
        ids.reverse()
        out.append((c, [node_names[n] for n in ids]))
    out.sort(key=lambda cp: (cp[0], cp[1]))
    return out


@pytest.mark.parametrize("case", DECODE_CASES)
def test_paths_to_host_by_hops_equals_the_per_slot_filter(case):
    names, dests, costs, paths, hops = run_decode_case(case)
    found = 0
    for job in range(len(dests)):
        want = paths_to_host_per_slot(costs, paths, names, job)
        assert paths_to_host(costs, paths, hops, names, job) == want, job
        # as _ksp_chunks hands them over: Python ints, once a chunk
        got = paths_to_host(costs.tolist(), paths, hops.tolist(), names, job)
        assert got == want, job
        assert all(type(c) is int for c, _ in got)
        found += len(want)
    assert found > 0
    if case == "failed_round":
        assert (costs[2:] >= int(INF_DIST)).all() and (hops[2:] == 0).all()
        assert (paths[2:] == -1).all() and found == 2
    if case == "padded_job":
        assert dests[-1] == 0 and (costs[:, -1] >= int(INF_DIST)).all()
        assert paths_to_host(costs, paths, hops, names, len(dests) - 1) == []
    if case == "one_hop":
        assert hops[0].tolist() == [1, 1]
        assert paths_to_host(costs, paths, hops, names, 0)[0] == (
            1, [names[0], names[1]])


@pytest.mark.parametrize("case", DECODE_CASES)
def test_the_kernel_writes_a_path_contiguously_and_counts_its_hops(case):
    """What the decode relies on: the nodes of path (i, job) are slots
    0..hops, nothing but -1 lies behind them, and the cost is INF_DIST
    exactly where the path is all -1 (then hops is 0)."""
    _names, dests, costs, paths, hops = run_decode_case(case)
    k, b, width = paths.shape
    assert costs.shape == hops.shape == (k, b) and b == len(dests)
    slot = np.arange(width)
    live = slot[None, None, :] <= hops[:, :, None]
    failed = costs >= int(INF_DIST)
    assert (paths[live & ~failed[:, :, None]] >= 0).all()
    assert (paths[~live] == -1).all()
    np.testing.assert_array_equal(failed, (paths == -1).all(axis=2))
    assert (hops[failed] == 0).all() and (hops[~failed] >= 1).all()
    np.testing.assert_array_equal(
        np.where(failed, 0, (paths >= 0).sum(axis=2) - 1), hops)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ksp_kernel_dist0_path_byte_equal(seed):
    """Production (_ksp_batch) always feeds the shared round-1
    distances via dist0 — the lax.cond/broadcast branch must produce
    byte-identical outputs to the self-solved path on the suite's
    adversarial graphs (asymmetric metrics, overloaded nodes, k=16)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n = 24
    adj, nbr, wgt, names = random_graph(rng, n)
    overloaded_ids = sorted(rng.choice(n, size=2, replace=False))
    over_mask = np.zeros(n, dtype=bool)
    over_mask[overloaded_ids] = True
    root_id = 0
    dests = np.array(
        sorted(rng.choice(np.arange(1, n), size=8, replace=False)),
        dtype=np.int32,
    )
    blocked = build_ksp_blocked(nbr, over_mask, root_id)
    dests = pad_dests(dests, root_id)
    ref_c, ref_p, ref_h = ksp_edge_disjoint_dense(
        nbr, wgt, blocked, np.int32(root_id), dests, k=16, max_hops=n - 1
    )
    # dist0 = the kernel's own unbanned round-1 distances (cost column
    # of a k=1 run gives dest distances only; derive the full vector
    # with an independent per-node run instead: k=1, dests=all nodes)
    all_dests = np.arange(n, dtype=np.int32)
    c1, _p1, _h1 = ksp_edge_disjoint_dense(
        nbr, wgt, blocked, np.int32(root_id), all_dests, k=1,
        max_hops=n - 1,
    )
    dist0 = np.asarray(c1[0]).astype(np.int32)
    dist0[root_id] = 0  # dest==root encodes as unreachable in costs
    got_c, got_p, got_h = ksp_edge_disjoint_dense(
        nbr, wgt, blocked, np.int32(root_id), dests, k=16,
        max_hops=n - 1, dist0=jnp.asarray(dist0),
    )
    np.testing.assert_array_equal(np.asarray(ref_c), np.asarray(got_c))
    np.testing.assert_array_equal(np.asarray(ref_p), np.asarray(got_p))
    np.testing.assert_array_equal(np.asarray(ref_h), np.asarray(got_h))


def test_ksp_relax_branches_agree(monkeypatch):
    """The unrolled d-loop relax (width <= _UNROLL_MAX_W) and the wide
    [Vp, D, B] gather fallback are the same fixpoint: run the kernel's
    undecorated function with the unroll bound forced to 0 (wide
    branch) and compare byte-for-byte against the normal jitted path
    (unrolled branch — every test graph is narrow). Guards the
    otherwise-dead wide branch and the branch equivalence itself."""
    import openr_tpu.ops.ksp as ksp_mod

    rng = np.random.default_rng(7)
    n = 24
    adj, nbr, wgt, names = random_graph(rng, n)
    over_mask = np.zeros(n, dtype=bool)
    over_mask[3] = True
    root_id = 0
    dests = np.array([2, 5, 9, 17], dtype=np.int32)
    blocked = build_ksp_blocked(nbr, over_mask, root_id)
    args = (nbr, wgt, blocked, np.int32(root_id), dests)
    ref = ksp_edge_disjoint_dense(*args, k=4, max_hops=n - 1)

    monkeypatch.setattr(ksp_mod, "_UNROLL_MAX_W", 0)
    wrapped = ksp_edge_disjoint_dense.__wrapped__  # undecorated: fresh trace
    import jax

    wide = jax.jit(wrapped, static_argnames=("k", "max_hops"))(
        *args, k=4, max_hops=n - 1
    )
    for a, b in zip(ref, wide):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shared_first_solve", [True, False])
def test_the_kernel_s_phases_carry_named_scopes(shared_first_solve):
    """A device trace of the KSP kernel reads by phase, as the split
    kernels' does (docs/Monitor.md "Spans"): the scopes are in the
    lowered program's op metadata. Lowering only: nothing runs."""
    import jax.numpy as jnp

    from openr_tpu.ops.ksp import _ksp_edge_disjoint_dense_jit

    v, d, b = 16, 4, 8
    lowered = _ksp_edge_disjoint_dense_jit.lower(
        jnp.zeros((v, d), jnp.int32),
        jnp.full((v, d), int(INF_DIST), jnp.int32),
        jnp.zeros((v, d), bool),
        jnp.int32(0),
        jnp.zeros((b,), jnp.int32),
        k=4,
        max_hops=v - 1,
        dist0=jnp.zeros((v,), jnp.int32) if shared_first_solve else None,
    )
    text = lowered.as_text(debug_info=True)
    scopes = ["round/ban_mask", "round/fixpoint", "round/walk", "round/emit"]
    for scope in scopes:
        assert scope in text, scope
    assert ("first_solve" in text) is shared_first_solve
