"""The split kernel's own loop counters (ops/spf_split.py): the int32
trailer of the packed RIB buffer against a NumPy replay of the same
loops, and the buffer's other sections unmoved by it."""

from __future__ import annotations

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.ops.spf import first_hop_matrix, lfa_matrix, pad_batch
from openr_tpu.ops.spf_split import (
    INF_DIST,
    SOLVE_COUNTERS,
    _small_frontier,
    batched_sssp_split_rib,
    batched_sssp_split_warm_rib,
    build_split_tables,
    rib_buffer_trailer,
    unpack_rib_buffer,
)
from openr_tpu.utils import topogen

INF = int(INF_DIST)


class Replay:
    """The kernel's three loops in NumPy (one Jacobi chunk: every graph
    here is below GS_MIN_VP), counting what the kernel counts."""

    def __init__(self, t: dict, tail_cap: int, tail_rounds_cap: int):
        self.t = t
        self.vp = t["vp"]
        self.dead = self.vp - 1
        self.cap = tail_cap
        self.rounds_cap = tail_rounds_cap
        # a round's expansion is the small one by its live count alone
        self.small = _small_frontier(tail_cap) or -1

    @staticmethod
    def relax(dist, nbr, wgt):
        g = dist[nbr].astype(np.int64)  # [R, W, B]
        cand = np.where(
            g < INF, np.minimum(g + wgt[:, :, None], INF), INF
        )
        return cand.min(axis=1)

    def dense_sweep(self, dist):
        t = self.t
        new = np.minimum(self.relax(dist, t["base_nbr"], t["base_wgt"]), dist)
        np.minimum.at(
            new, t["ov_ids"], self.relax(dist, t["ov_nbr"], t["ov_wgt"])
        )
        return new

    def cold_start(self, roots, tail_threshold):
        dist = np.full((self.vp, len(roots)), INF, np.int64)
        dist[roots, np.arange(len(roots))] = 0
        changed = np.zeros(self.vp, bool)
        changed[roots] = True
        n_changed, sweeps = tail_threshold + 1, 0
        while n_changed > tail_threshold and sweeps < self.vp:
            new = self.dense_sweep(dist)
            changed = (new < dist).any(axis=1)
            n_changed, dist, sweeps = int(changed.sum()), new, sweeps + 1
        frontier = np.nonzero(changed)[0][: self.cap]
        return dist, frontier, n_changed > self.cap, sweeps

    def tail_then_net(self, dist, frontier, spilled, pull_frontier):
        t = self.t
        rounds = small_rounds = 0
        while len(frontier) and not spilled and rounds < self.rounds_cap:
            reach = t["out_nbr"][frontier].reshape(-1)
            if pull_frontier:
                reach = np.concatenate([reach, frontier])
            exp = np.unique(reach)
            exp = exp[exp != self.dead]
            spilled = len(exp) > self.cap
            small_rounds += len(frontier) <= self.small
            rows = exp[: self.cap]
            dist2 = dist.copy()
            np.minimum.at(
                dist2, rows,
                self.relax(dist, t["base_nbr"][rows], t["base_wgt"][rows]),
            )
            np.minimum.at(
                dist2, t["ov_ids"],
                self.relax(dist, t["ov_nbr"], t["ov_wgt"]),
            )
            ov = t["ov_ids"]
            nxt = np.unique(np.concatenate([
                rows[(dist2[rows] < dist[rows]).any(axis=1)],
                ov[(dist2[ov] < dist[ov]).any(axis=1)],
            ]))
            spilled = spilled or len(nxt) > self.cap
            frontier, dist, rounds = nxt[: self.cap], dist2, rounds + 1
        changed, net = bool(spilled or len(frontier)), 0
        while changed and net < self.vp:
            new = self.dense_sweep(dist)
            changed, dist, net = bool((new < dist).any()), new, net + 1
        return dist, rounds, net, int(spilled), small_rounds


def er_case(n=600, deg=6, seed=3, max_metric=32, batch=8):
    es, ed, em, _vp, nn, _e = topogen.erdos_renyi_csr(
        n, avg_degree=deg, seed=seed, max_metric=max_metric
    )
    t = build_split_tables(es, ed, em, nn)
    b = pad_batch(batch)
    # column 0 the root, the others its "neighbors" (any nodes do)
    roots = (np.arange(b, dtype=np.int32) * 7) % nn
    nbr_ids = roots[1:].copy()
    nbr_metric = np.arange(1, b, dtype=np.int32)
    nbr_over = np.zeros(b - 1, bool)
    return t, roots, nbr_ids, nbr_metric, nbr_over, (es, ed, em, nn)


def dev_tables(t):
    return [jnp.asarray(t[k]) for k in (
        "base_nbr", "base_wgt", "ov_ids", "ov_nbr", "ov_wgt", "out_nbr")]


def cold(t, roots, nbr_ids, nbr_metric, nbr_over, **kw):
    dist, packed = batched_sssp_split_rib(
        *dev_tables(t), jnp.zeros(t["vp"], bool), jnp.asarray(roots),
        jnp.asarray(nbr_metric), jnp.asarray(nbr_ids), jnp.asarray(nbr_over),
        jnp.int32(int(roots[0])), **kw,
    )
    return np.asarray(dist), np.asarray(packed)


@pytest.mark.parametrize(
    "tail_threshold,tail_cap,spills",
    [(64, 1024, False), (16, 1024, False), (10_000, 1024, False),
     (64, 8, True), (64, 256, True), (16, 256, False), (64, 8192, False)],
)
def test_cold_trailer_equals_a_numpy_replay(tail_threshold, tail_cap, spills):
    t, roots, nbr_ids, nbr_metric, nbr_over, _g = er_case()
    dist, buf = cold(
        t, roots, nbr_ids, nbr_metric, nbr_over,
        tail_threshold=tail_threshold, tail_cap=tail_cap,
    )
    got = rib_buffer_trailer(buf)
    assert tuple(got) == SOLVE_COUNTERS
    rp = Replay(t, tail_cap, 64)
    d0, frontier, spilled, sweeps = rp.cold_start(roots, tail_threshold)
    want_dist, rounds, net, spilled, small_rounds = rp.tail_then_net(
        d0, frontier, spilled, pull_frontier=False
    )
    np.testing.assert_array_equal(dist, want_dist)
    assert got == {
        "dense_sweeps": sweeps, "tail_rounds": rounds, "net_sweeps": net,
        "spilled": spilled, "tail_small_rounds": small_rounds,
    }
    assert got["tail_small_rounds"] <= got["tail_rounds"]
    if tail_cap == 8:  # too small for two expansion capacities
        assert got["tail_small_rounds"] == 0
    if (tail_threshold, tail_cap) == (64, 8192):  # of either capacity
        assert 0 < got["tail_small_rounds"] < got["tail_rounds"]
    assert bool(got["spilled"]) is spills
    assert got["dense_sweeps"] >= 1
    if spills:
        assert got["net_sweeps"] >= 1
    elif tail_threshold < 10_000:
        # the dense phase handed over to the tail and the tail finished
        assert got["tail_rounds"] >= 1 and got["net_sweeps"] == 0


@pytest.mark.parametrize("with_lfa", [False, True])
def test_unpack_is_unmoved_by_the_trailer_cold(with_lfa):
    t, roots, nbr_ids, nbr_metric, nbr_over, _g = er_case(n=300, seed=5)
    vp, b = t["vp"], len(roots)
    dist, buf = cold(
        t, roots, nbr_ids, nbr_metric, nbr_over, with_lfa=with_lfa
    )
    sections = 2 if with_lfa else 1
    assert buf.dtype == np.uint8
    assert buf.size == 4 * vp + sections * (b - 1) * (vp // 8) + 20
    d_root, fh, lfa = unpack_rib_buffer(buf, vp, b, with_lfa)
    assert d_root.tobytes() == dist[:, 0].astype(np.int32).tobytes()
    want_fh = np.asarray(first_hop_matrix(
        jnp.asarray(dist), jnp.asarray(nbr_metric), jnp.asarray(nbr_ids),
        jnp.asarray(nbr_over),
    ))
    assert fh.tobytes() == want_fh.tobytes()
    if with_lfa:
        want_lfa = np.asarray(lfa_matrix(
            jnp.asarray(dist), jnp.int32(int(roots[0])),
            jnp.asarray(nbr_ids), jnp.asarray(nbr_over),
        ))
        assert lfa.tobytes() == want_lfa.tobytes()
    else:
        assert lfa is None
    assert rib_buffer_trailer(buf)["spilled"] == 0


def test_warm_trailer_equals_a_numpy_replay_and_the_cold_result():
    """One edge lowered: the old distances are upper bounds, the seed is
    the lowered edge's head; the warm start has no dense phase."""
    t_old, roots, nbr_ids, nbr_metric, nbr_over, (es, ed, em, nn) = er_case(
        n=400, seed=9
    )
    old_dist, _ = cold(t_old, roots, nbr_ids, nbr_metric, nbr_over)
    # lower the heaviest live edge to 1
    i = int(np.argmax(np.where(em < INF, em, -1)))
    em2 = em.copy()
    em2[i] = 1
    t = build_split_tables(es, ed, em2, nn)
    assert t["vp"] == t_old["vp"]
    seed = np.zeros(t["vp"], bool)
    seed[int(ed[i])] = True
    dist, packed = batched_sssp_split_warm_rib(
        *dev_tables(t), jnp.zeros(t["vp"], bool), jnp.asarray(roots),
        jnp.asarray(nbr_metric), jnp.asarray(nbr_ids), jnp.asarray(nbr_over),
        jnp.asarray(old_dist), jnp.asarray(seed), tail_cap=1024,
    )
    dist, buf = np.asarray(dist), np.asarray(packed)
    rp = Replay(t, 1024, 64)
    want_dist, rounds, net, spilled, small_rounds = rp.tail_then_net(
        old_dist.astype(np.int64), np.nonzero(seed)[0], False,
        pull_frontier=True,
    )
    np.testing.assert_array_equal(dist, want_dist)
    got = rib_buffer_trailer(buf)
    assert tuple(got) == SOLVE_COUNTERS
    assert got == {
        "dense_sweeps": 0, "tail_rounds": rounds, "net_sweeps": net,
        "spilled": 0, "tail_small_rounds": small_rounds,
    }
    assert rounds >= 1 and spilled == 0
    # one seed: the first round expands at the small capacity
    assert 1 <= small_rounds <= rounds
    # same fixpoint, same bytes before the trailer as a cold solve of
    # the new graph
    cold_dist, cold_buf = cold(t, roots, nbr_ids, nbr_metric, nbr_over)
    np.testing.assert_array_equal(dist, cold_dist)
    assert buf[:-20].tobytes() == cold_buf[:-20].tobytes()
    d_root, fh, _ = unpack_rib_buffer(buf, t["vp"], len(roots), False)
    assert d_root.tobytes() == dist[:, 0].tobytes()
    assert fh.shape == (len(roots) - 1, t["vp"])


def test_the_solver_adds_the_trailer_to_its_kernel_stats():
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    ls, ps, _csr = topogen.erdos_renyi_lsdb(
        220, avg_degree=6, seed=7, max_metric=64
    )
    solver = TpuSpfSolver(native_rib="off")
    solver.compute_routes(ls, ps, "node-0")
    st = solver.spf_kernel_stats
    assert st["dense_sweeps"] >= 1 and st["tail_spills"] == 0
    first = st["dense_sweeps"] + st["tail_rounds"]
    small = st["tail_small_rounds"]
    assert 0 <= small <= st["tail_rounds"]
    solver.compute_routes(ls, ps, "node-0")
    assert st["dense_sweeps"] + st["tail_rounds"] == 2 * first
    assert st["tail_small_rounds"] == 2 * small
    assert st["warm_tail_rounds"] == 0  # no warm start ran
    assert st["warm_tail_small_rounds"] == 0
    # the six phases of the call, from its span record
    assert set(solver.last_phase_ms) == {
        "prepare", "solve", "unpack", "election", "assembly", "mpls"
    }
    assert all(v >= 0.0 for v in solver.last_phase_ms.values())
    assert solver.last_phase_ms["solve"] > 0.0


@pytest.mark.parametrize(
    "name,counter,moves",
    [
        ("spf_warm_small_rounds", "decision.spf.warm_tail_small_rounds",
         "event_to_fib_p50_ms"),
        ("spf_cold_small_tail_rounds", "solver.tail_small_rounds",
         "full_rib_ms"),
    ],
)
def test_the_small_round_metrics_read_the_counters_the_solver_keeps(
    name, counter, moves
):
    """The benchmark's two metrics name counters that exist: the key of
    `spf_kernel_stats` behind the `decision.spf.` / `solver.` prefix the
    exporters put before every key."""
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    root = Path(__file__).resolve().parents[1]
    spec = json.loads(
        (root / "perfbench" / "layer_metrics" / f"{name}.json").read_text()
    )
    assert spec["name"] == name and spec["reader"] == "counter_per_event"
    assert spec["args"] == {"counter": counter}
    assert spec["layer"] == "SPF kernel" and spec["moves"] == moves
    key = counter.rsplit(".", 1)[1]
    assert key in TpuSpfSolver(native_rib="off").spf_kernel_stats
    entry = [
        m for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == name
    ]
    assert len(entry) == 1 and entry[0]["better"] == "higher"
    assert entry[0]["source"] == "program_counter"
    assert entry[0]["moves"] == moves and entry[0]["layer"] == "SPF kernel"
