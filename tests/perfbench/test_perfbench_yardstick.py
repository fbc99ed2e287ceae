"""The yardstick's own parts: generators, reference, comparison, work
count, peaks, statistics, trace reduction."""

import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import compare, events, reference, topo, trace_reduce, work
from perfbench.stats import percentile, statistic

DATA = Path(__file__).resolve().parent / "data"


# ----------------------------------------------------------------- generators


@pytest.mark.parametrize("k", [2, 4, 8])
def test_fat_tree_is_the_programs_fat_tree(k):
    from openr_tpu.utils import topogen

    g = topo.fat_tree(k)
    adj_dbs, prefix_dbs = topogen.fat_tree(k)
    per_node: dict[int, list] = {}
    for s, d, m in zip(g.src.tolist(), g.dst.tolist(), g.metric.tolist()):
        per_node.setdefault(s, []).append((topo.node_name(d), m))
    for i, db in enumerate(adj_dbs):
        assert db.this_node_name == topo.node_name(i)
        assert db.node_label == topo.node_label(i)
        assert per_node[i] == [(a.other_node_name, a.metric) for a in db.adjacencies]
        assert [a.if_name for a in db.adjacencies] == [
            topo.if_name(i, int(a.other_node_name[5:])) for a in db.adjacencies]
        assert str(prefix_dbs[i].prefix_entries[0].prefix.prefix) == topo.loopback(i)


def test_erdos_renyi_is_the_programs_and_ignores_nothing_but_the_graph_seed():
    from openr_tpu.utils import topogen

    g = topo.erdos_renyi(500, 6, 64, graph_seed=3)
    src, dst, met, _vp, n, e = topogen.erdos_renyi_csr(500, 6, 3, 64)
    assert (n, e) == (g.n, g.num_edges)
    assert (src[:e] == g.src).all() and (dst[:e] == g.dst).all()
    assert (met[:e] == g.metric).all()
    assert (np.diff(g.dst) >= 0).all(), "sorted by destination"
    other = topo.erdos_renyi(500, 6, 64, graph_seed=4)
    assert not (other.metric == g.metric).all()


def test_edge_slot_and_set_metric():
    g = topo.fat_tree(4)
    u, v = topo.fat_tree_agg(g, 1, 0), topo.fat_tree_tor(g, 1, 1)
    g.set_metric(u, v, 10)
    assert g.metric[g.edge_slot(u, v)] == 10 == g.metric[g.edge_slot(v, u)]
    assert int(g.src[g.edge_slot(u, v)]) == u and int(g.dst[g.edge_slot(u, v)]) == v
    assert (g.metric == 10).sum() == 2
    with pytest.raises(KeyError):
        g.edge_slot(0, 1)  # two cores are never adjacent
    with pytest.raises(ValueError):
        topo.build({"generator": "moebius", "k": 3})


# --------------------------------------------------------------------- events


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 4_000_000_000])
def test_flap_sequence_is_seeded_and_never_raises_more_than_two(seed):
    g = topo.fat_tree(8)
    root = events.root_of(g, {"tor": [0, 0]})
    pool = events.link_pool(g, "fat_tree_tor_agg", root)
    assert len(pool) == 7 * 4 * 4  # every pod but the root's own
    own = {topo.fat_tree_agg(g, 0, i) for i in range(4)}
    assert not own & set(pool[:, 0].tolist())
    traffic = {"max_raised": 2, "raised_metric": 10, "restored_metric": 1}

    def draw(n):
        flaps = events.flap_sequence(pool, np.random.default_rng(seed), traffic)
        return [next(flaps) for _ in range(n)]

    seq = draw(40)
    assert seq == draw(40), "the same seed gives the same events"
    raised = set()
    for link, metric in seq:
        if metric == 10:
            assert link not in raised
            raised.add(link)
        else:
            raised.remove(link)
        assert len(raised) <= 2
    assert [m for _l, m in seq[:4]] == [10, 10, 1, 10]


def test_link_pool_of_any_link_keeps_off_the_root():
    g = topo.erdos_renyi(300, 6, 64, graph_seed=0)
    pool = events.link_pool(g, "any_not_at_root", 0)
    assert (pool != 0).all() and (pool[:, 0] < pool[:, 1]).all()
    assert len(pool) == g.meta["links"] - int((g.src == 0).sum())
    with pytest.raises(ValueError):
        events.link_pool(g, "every_other", 0)


class FakeMeter:
    """Compiles on the rounds it is told to."""

    def __init__(self, compiling_rounds):
        self.compiling, self.round = set(compiling_rounds), 0

    def mark(self):
        self.round += 1
        return self.round

    def since(self, mark):
        return {"compiles": int(mark in self.compiling), "backend_compiles": 0}


@pytest.mark.parametrize("compiling,want", [
    ((), 3),            # the least, nothing compiles
    ((1, 2), 5),        # quiet for 3 after round 2
    ((1, 4), 7),        # a late compile starts the count again
])
def test_warm_up_runs_until_quiet(compiling, want):
    traffic = {"warmup_events": 3, "warmup_quiet": 3}
    rounds = list(events.warm_up_rounds(FakeMeter(compiling), traffic))
    assert rounds == list(range(1, want + 1))


def test_warm_up_that_never_quiets_is_an_error():
    traffic = {"warmup_events": 1, "warmup_quiet": 2, "warmup_max": 6}
    with pytest.raises(RuntimeError, match="still compiling after 6"):
        list(events.warm_up_rounds(FakeMeter(range(1, 100)), traffic))


# ------------------------------------------------------- reference, comparison


def program_tables(g, root, changed=()):
    """The program's scalar oracle on the same graph, in plain form: a
    second witness for the reference, not the reference."""
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.oracle import compute_routes

    from perfbench.drivers.decision_fib import program_dbs

    adj_dbs, prefix_dbs = program_dbs(g)
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    rdb = compute_routes(ls, ps, topo.node_name(root), vectorize=False)
    return (
        compare.plain_unicast(e.to_unicast_route() for e in rdb.unicast_routes.values()),
        compare.plain_mpls(e.to_mpls_route() for e in rdb.mpls_routes.values()),
    )


def graphs():
    fabric = topo.fat_tree(4)
    fabric.set_metric(topo.fat_tree_agg(fabric, 2, 1), topo.fat_tree_tor(fabric, 2, 0), 10)
    yield "fat_tree", fabric, topo.fat_tree_tor(fabric, 0, 0)
    yield "erdos_renyi", topo.erdos_renyi(300, 6, 64, graph_seed=0), 0


@pytest.mark.parametrize("name,g,root", list(graphs()), ids=lambda x: x if isinstance(x, str) else "")
def test_reference_agrees_with_the_programs_scalar_oracle(name, g, root):
    want_u, want_m = reference.tables(g, root)
    got_u, got_m = program_tables(g, root)
    assert compare.count_differences(got_u, want_u) == (0, [])
    assert compare.count_differences(got_m, want_m) == (0, [])
    assert len(want_u) == g.n - 1 == len(want_m)


@pytest.mark.parametrize("name,g,root", list(graphs()), ids=lambda x: x if isinstance(x, str) else "")
def test_control_without_ecmp_is_not_correct(name, g, root):
    """The control breaks the ECMP guarantee (one next hop where several
    tie) and has to fail the comparison at limit 0, in both tables."""
    want_u, want_m = reference.tables(g, root)
    ctl_u, ctl_m = reference.tables(g, root, ecmp=False)
    nu, _ = compare.count_differences(ctl_u, want_u)
    nm, _ = compare.count_differences(ctl_m, want_m)
    assert nu > 0 and nm > 0
    assert all(len(nhs) == 1 for nhs in ctl_u.values())


def test_count_differences_sees_missing_extra_and_changed():
    want = {"a": (1,), "b": (2,), "c": (3,)}
    got = {"a": (1,), "b": (9,), "d": (4,)}
    n, examples = compare.count_differences(got, want)
    assert n == 3 and examples == ["b", "c", "d"]


# ------------------------------------------------------------ work and peaks


def test_work_on_a_hand_counted_graph():
    # 4 nodes, 6 directed edges, 2 sources, 40 bytes fetched:
    # 6*2*4 + 6*8 + 2*4*2*4 + 40 = 48 + 48 + 64 + 40
    assert work.solve_least_bytes(nodes=4, edges=6, batch=2, out_bytes=40) == 200
    assert work.solve_least_ops(edges=6, batch=2) == 24
    # 200 bytes at 819 GB/s against a kernel of 1 us
    share = work.roofline_share_pct("TPU v5 lite", 4, 6, 2, 40, kernel_s=1e-6)
    assert share == pytest.approx(100 * (200 / 819e9) / 1e-6)


def test_unknown_device_kind_raises():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        work.peaks("cpu")
    with pytest.raises(KeyError):
        work.roofline_share_pct("TPU v9", 4, 6, 2, 40, kernel_s=1e-6)


# ------------------------------------------------------------------ statistics


def test_percentile_and_statistics():
    values = [float(v) for v in range(1, 101)]
    assert percentile(values, 0.5) == pytest.approx(np.percentile(values, 50))
    assert percentile(values, 0.95) == pytest.approx(np.percentile(values, 95))
    obs = {"events": 4, "window_s": 2.0}
    assert statistic("window_per_event_ms", [], obs) == 500.0
    assert statistic("p50", [3.0, 1.0, 2.0], obs) == 2.0
    assert statistic("mean", [1.0, 2.0, 6.0], obs) == 3.0
    assert statistic("p95", [], obs) is None
    with pytest.raises(ValueError):
        statistic("mode", [1.0], obs)


# -------------------------------------------------------------- trace reduction


def synthetic_trace():
    ms = 1_000_000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_batched_sssp_split_warm_rib(123)", 10 * ms, 4 * ms],
                ["jit_scatter(9)", 16 * ms, 1 * ms],
                ["jit_batched_sssp_split_warm_rib(123)", 30 * ms, 6 * ms],
                ["jit_batched_sssp_split_warm_rib(123)", 98 * ms, 4 * ms],
            ]},
            {"name": "XLA Ops", "events": [
                ["while.1", 10 * ms, 4 * ms], ["fusion.2", 11 * ms, 2 * ms],
                ["scatter.3", 16 * ms, 1 * ms],
                ["while.1", 30 * ms, 6 * ms],
                ["while.1", 98 * ms, 4 * ms],  # runs past the window's end
            ]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["perfbench:window", 0, 100 * ms]]},
            {"name": "worker", "events": [["spf:warm_solve", 17 * ms, 12 * ms]]},
        ]},
    ]}


def test_trace_reduce_on_a_synthetic_trace():
    got = trace_reduce.reduce_events(synthetic_trace())
    # busy: [10,14) + [16,17) + [30,36) + [98,100) clipped = 13 ms of 100
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.013)
    assert got["devices"] == 1
    # the program that runs past the window's end is not a whole call
    assert got["kernels_s"]["batched_sssp_split_warm_rib"] == pytest.approx(0.010)
    assert got["kernel_calls"]["batched_sssp_split_warm_rib"] == 2
    assert got["kernels_s"]["scatter"] == pytest.approx(0.001)
    gaps = dict(got["idle_gaps"])
    # [17,30) lies under spf:warm_solve; [0,10), [14,16), [36,98) do not
    assert gaps["spf:warm_solve"] == pytest.approx(0.013)
    assert gaps["host: other"] == pytest.approx(0.010 + 0.002 + 0.062)
    assert got["busy_s"] + sum(gaps.values()) == pytest.approx(got["window_s"])
    assert got["device_ops"][0][0] == "while.1"


def test_trace_reduce_without_a_device_reads_nothing():
    got = trace_reduce.reduce_events({"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["perfbench:window", 0, 5_000_000]]}]}]})
    assert got["busy_s"] == 0.0 and got["kernels_s"] == {} and got["devices"] == 0


def test_module_fn_names():
    assert trace_reduce.module_fn("jit_batched_sssp_split_rib(1776)") == "batched_sssp_split_rib"
    assert trace_reduce.module_fn("jit__where") == "_where"
    assert trace_reduce.module_fn("scatter") == "scatter"


def test_trace_reduce_on_the_recorded_trace():
    """A cut of a trace recorded on the v5e (fabric10k.metric_flap, PR 24),
    kept as plain JSON; the numbers are fixed by the recording."""
    with open(DATA / "flap_trace_cut.json") as f:
        cut = json.load(f)
    got = trace_reduce.reduce_events(cut["trace"])
    want = cut["expected"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for fn, seconds in want["kernels_s"].items():
        assert got["kernels_s"][fn] == pytest.approx(seconds, rel=1e-9)
    assert got["kernel_calls"] == want["kernel_calls"]
    assert 0 < got["busy_s"] < got["window_s"]
