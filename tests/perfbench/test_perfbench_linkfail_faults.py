"""The configuration `fabric_linkfail`'s generator, reference and driver
`decision_fib_linkfail`: the standing set and the pool as the
configuration states them, the order of the events, the reference against
the plain one and its control, and the driver's planted faults in a file
of its own, as perfbench/README.md asks of a new driver: what has to make
the rehearsal cell of `fabric_linkfail.tor_uplink_down_up` read
`correct: false`, in-process on the CPU."""

import ast
import json
import time

import numpy as np
import pytest
from perfbench_util import REPO, lose_traces_in_the_window, tiny_checkout

from perfbench import compare, reference as plain, topo
from perfbench.drivers import decision_fib_linkfail
from perfbench.references import fabric_linkfail
from perfbench.topologies import fat_tree_failed

CELL = "tiny_fabric_linkfail.tor_uplink_down_up"


def config_of(name: str) -> dict:
    return json.loads(
        (REPO / "perfbench" / "configs" / f"{name}.json").read_text())


def graph_of(name: str) -> topo.Graph:
    return topo.build(config_of(name)["topology"])


def switches_of(links) -> list[int]:
    return [s for link in links for s in link]


def pod_of(g: topo.Graph, node: int) -> int | None:
    """The pod of an aggregation switch or a ToR; None for a spine."""
    n_core, n_agg, half = g.meta["n_core"], g.meta["n_agg"], g.meta["half"]
    if node < n_core:
        return None
    return ((node - n_core) % n_agg) // half


# ------------------------------------------------------- the generator


@pytest.mark.parametrize("name", ["tiny_fabric_linkfail", "fabric_linkfail"])
def test_the_standing_set_is_what_the_configuration_states(name):
    g = graph_of(name)
    spec = config_of(name)["topology"]
    plain_tree = topo.fat_tree(spec["k"])
    assert np.array_equal(g.src, plain_tree.src)
    assert np.array_equal(g.dst, plain_tree.dst)
    assert np.array_equal(g.metric, plain_tree.metric)
    failed = g.meta["failed"]
    n_core, n_tor0 = g.meta["n_core"], g.meta["n_core"] + g.meta["n_agg"]
    tor_agg = [ln for ln in failed if ln[1] >= n_tor0]
    agg_spine = [ln for ln in failed if ln[0] < n_core]
    assert (len(tor_agg), len(agg_spine)) == (
        spec["failed_tor_agg"], spec["failed_agg_spine"])
    for u, v in failed:
        assert u < v and g.edge_slot(u, v) >= 0 and g.edge_slot(v, u) >= 0
    ends = switches_of(failed)
    assert len(ends) == len(set(ends)), "no switch with more than one"
    assert 0 not in {pod_of(g, s) for s in ends}, "none of pod 0"
    pool = g.meta["link_pools"]["tor_agg_up"]
    assert not set(pool.ravel().tolist()) & set(ends)
    assert all(pod_of(g, int(a)) == pod_of(g, int(t)) != 0 for a, t in pool)
    k, half = spec["k"], g.meta["half"]
    # every ToR<->agg link of the other pods, less the standing set's ends
    assert len(pool) + sum(
        1 for p in range(1, k) for a in range(half) for t in range(half)
        if {topo.fat_tree_agg(g, p, a), topo.fat_tree_tor(g, p, t)} & set(ends)
    ) == (k - 1) * half * half


def test_one_standing_set_for_every_seed_and_another_for_another_graph_seed():
    a = fat_tree_failed.build(8, 3, 3, 0)
    b = fat_tree_failed.build(8, 3, 3, 0)
    c = fat_tree_failed.build(8, 3, 3, 1)
    assert a.meta["failed"] == b.meta["failed"] != c.meta["failed"]
    assert np.array_equal(a.meta["link_pools"]["tor_agg_up"],
                          b.meta["link_pools"]["tor_agg_up"])


def test_the_generator_refuses_what_it_cannot_draw():
    # k = 4: pods 1-3 hold 6 ToRs; 7 ToR<->agg links need 7 of them
    with pytest.raises(ValueError, match="cannot draw"):
        fat_tree_failed.build(4, 7, 0, 0)


def test_a_kept_graph_holds_its_own_copy_of_the_failed_set():
    g = fat_tree_failed.build(4, 1, 1, 0)
    down = set(g.meta["failed"])
    kept = fat_tree_failed.as_published(g, down)
    down.add((5, 13))
    assert kept.meta["failed"] == g.meta["failed"]
    assert (5, 13) not in kept.meta["failed"]
    assert kept.meta is not g.meta
    assert kept.meta["link_pools"] is g.meta["link_pools"]


# -------------------------------------------------------- the event order


@pytest.mark.parametrize("k,seed", [(4, 1), (6, 2), (8, 2147483659)])
def test_down_down_up_the_oldest_and_never_two_at_a_switch(k, seed):
    g = fat_tree_failed.build(k, 1, 1, 0)
    pool = {tuple(r) for r in g.meta["link_pools"]["tor_agg_up"].tolist()}
    standing = set(switches_of(g.meta["failed"]))
    events = decision_fib_linkfail.link_sequence(
        g, np.random.default_rng(seed), {"max_down": 2})
    held: list = []
    kinds = []
    for _ in range(200):
        link, is_down = next(events)
        kinds.append(is_down)
        if is_down:
            assert link in pool
            assert not set(link) & (standing | set(switches_of(held)))
            held.append(link)
        else:
            assert link == held.pop(0), "the oldest comes back"
        assert len(held) <= 2
    assert kinds[:5] == [True, True, False, True, False]
    first = decision_fib_linkfail.link_sequence(
        g, np.random.default_rng(seed), {"max_down": 2})
    again = decision_fib_linkfail.link_sequence(
        g, np.random.default_rng(seed), {"max_down": 2})
    assert [next(first) for _ in range(20)] == [next(again) for _ in range(20)]


# --------------------------------------------------------- the reference


def test_with_no_link_down_it_is_the_plain_reference():
    g = fat_tree_failed.as_published(fat_tree_failed.build(6, 2, 2, 0), ())
    root = topo.fat_tree_tor(g, 0, 0)
    assert fabric_linkfail.tables(g, root) == plain.tables(g, root)


@pytest.mark.parametrize("k,tor_agg,agg_spine", [(4, 1, 1), (6, 2, 2), (8, 3, 3)])
def test_the_control_differs_by_the_tors_of_the_tor_uplinks_down(
        k, tor_agg, agg_spine):
    """The failed set ignored: each ToR with an uplink down keeps the next
    hop through that uplink's plane (one loopback, one label); an
    agg<->spine link down moves nothing here."""
    g = fat_tree_failed.build(k, tor_agg, agg_spine, 0)
    root = topo.fat_tree_tor(g, 0, 0)
    want_u, want_m = fabric_linkfail.tables(g, root)
    ctl_u, ctl_m = fabric_linkfail.tables(g, root, control=True)
    nu, eg_u = compare.count_differences(ctl_u, want_u)
    nm, _ = compare.count_differences(ctl_m, want_m)
    assert (nu, nm) == (tor_agg, tor_agg)
    tors = {t for _a, t in g.meta["failed"] if t >= g.meta["n_core"] + g.meta["n_agg"]}
    assert set(eg_u) <= {topo.loopback(t) for t in tors}
    for t in tors:
        assert len(want_u[topo.loopback(t)]) == g.meta["half"] - 1
        assert len(ctl_u[topo.loopback(t)]) == g.meta["half"]


def test_a_failed_link_carries_nothing_in_either_direction():
    """The reference's graph has neither directed edge of a failed link."""
    g = fat_tree_failed.build(6, 2, 2, 0)
    cut = fabric_linkfail.without(g, g.meta["failed"])
    assert cut.num_edges == g.num_edges - 2 * len(g.meta["failed"])
    have = set(zip(cut.src.tolist(), cut.dst.tolist()))
    for u, v in g.meta["failed"]:
        assert (u, v) not in have and (v, u) not in have


def test_the_reference_and_the_generator_import_nothing_of_the_program():
    for path in ("perfbench/references/fabric_linkfail.py",
                 "perfbench/topologies/fat_tree_failed.py"):
        tree = ast.parse((REPO / path).read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                assert not (node.module or "").startswith("openr_tpu"), path
            elif isinstance(node, ast.Import):
                assert not any(a.name.startswith("openr_tpu")
                               for a in node.names), path


# ----------------------------------------------------------------- faults


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("perfbench_linkfail"))


def run_in_process(checkout, seed=11, seconds=1.0):
    from perfbench import run

    rc, line = run.run_cell(checkout, CELL, seed, seconds, False, time.perf_counter())
    assert rc == 0
    return line


def test_a_sound_run_is_correct(checkout):
    line = run_in_process(checkout)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 < line["attempted"]
    assert line["compared"]["tables_missing"]["value"] == 0


def the_standing_links_kept_by_the_solver(monkeypatch):
    """The CSR the solver works on is built as if the switches of the
    standing set still advertised their failed links: those carry routes,
    as the reference's control has it, from the first RIB on. (With every
    withdrawal ignored no event would move a route, and an event that
    tells Fib nothing only times out.)"""
    from openr_tpu.decision.linkstate import LinkState
    from perfbench.drivers.decision_fib import program_dbs

    g = graph_of("tiny_fabric_linkfail")
    whole = {db.this_node_name: db for db in program_dbs(g)[0]}
    standing = {topo.node_name(s) for s in switches_of(g.meta["failed"])}
    real = LinkState._build_csr

    def with_the_standing_links(self):
        saved = self._adj_dbs
        self._adj_dbs = {n: whole[n] if n in standing else db
                         for n, db in saved.items()}
        try:
            return real(self)
        finally:
            self._adj_dbs = saved

    monkeypatch.setattr(LinkState, "_build_csr", with_the_standing_links)


def nothing_programmed_after_the_first_event(monkeypatch):
    """The handler acknowledges every update and applies the first alone
    (the first warm-up event's; the first RIB comes by `sync_fib`): its
    table keeps that one link down whatever went down or up since."""
    from openr_tpu.fib import MockFibHandler

    real_u = MockFibHandler.add_unicast_routes
    real_m = MockFibHandler.add_mpls_routes
    calls = {"u": 0, "m": 0}

    async def stale_u(self, client_id, routes):
        calls["u"] += 1
        if calls["u"] <= 1:
            await real_u(self, client_id, routes)

    async def stale_m(self, client_id, routes):
        calls["m"] += 1
        if calls["m"] <= 1:
            await real_m(self, client_id, routes)

    monkeypatch.setattr(MockFibHandler, "add_unicast_routes", stale_u)
    monkeypatch.setattr(MockFibHandler, "add_mpls_routes", stale_m)


@pytest.mark.parametrize("fault", [
    the_standing_links_kept_by_the_solver,
    nothing_programmed_after_the_first_event])
def test_a_planted_fault_reads_not_correct(checkout, monkeypatch, fault):
    fault(monkeypatch)
    line = run_in_process(checkout, seconds=2.0)
    assert line["correct"] is False
    # a ToR uplink moves that ToR's loopback and its label alike
    assert line["compared"]["unicast_routes_differ"]["value"] > 0
    assert line["compared"]["mpls_routes_differ"]["value"] > 0


def test_a_check_that_shares_the_failed_set_with_a_later_state_is_caught(
        checkout, monkeypatch):
    """`Graph.copy()` shares `meta`: were the kept graphs to share the
    live failed set, every table would be compared with the last state,
    and the tables taken earlier differ from it."""
    live = []

    def shared(g, failed):
        live.append(failed)
        kept = g.copy()
        kept.meta["failed"] = failed  # the set the driver goes on changing
        return kept

    monkeypatch.setattr(fat_tree_failed, "as_published", shared)
    line = run_in_process(checkout, seconds=2.0)
    assert live, "the driver took its kept graphs from as_published"
    assert line["correct"] is False
    assert line["compared"]["unicast_routes_differ"]["value"] > 0


def test_an_event_that_never_reaches_fib_programmed_fails_the_run(
        checkout, monkeypatch):
    traffic = checkout / "perfbench" / "traffic" / "tor_uplink_down_up.json"
    saved = traffic.read_text()
    spec = json.loads(saved)
    spec["event_timeout_s"] = 1.0
    lose_traces_in_the_window(monkeypatch)
    try:
        traffic.write_text(json.dumps(spec))
        line = run_in_process(checkout, seconds=3.0)
    finally:
        traffic.write_text(saved)
    assert line["failed"] == 1 and line["correct"] is False
    assert line["compared"]["events_failed"]["value"] == 1
