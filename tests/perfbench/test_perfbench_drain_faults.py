"""The driver `decision_fib_drain` (PR 38): the order of its events, and its
planted faults in a file of its own, as perfbench/README.md asks of a new
driver: what has to make the rehearsal cell of
`fabric_drain.agg_drain_undrain` read `correct: false`, in-process on the
CPU."""

import dataclasses
import json
import time

import numpy as np
import pytest
from perfbench_util import REPO, lose_traces_in_the_window, tiny_checkout

from perfbench import topo
from perfbench.drivers import decision_fib_drain
from perfbench.topologies import fat_tree_drained

CELL = "tiny_fabric_drain.agg_drain_undrain"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("perfbench_drain"))


def run_in_process(checkout, seed=11, seconds=1.0):
    from perfbench import run

    rc, line = run.run_cell(checkout, CELL, seed, seconds, False, time.perf_counter())
    assert rc == 0
    return line


# ------------------------------------------------------------ the event order


@pytest.mark.parametrize("k,aggs,seed", [(4, 1, 1), (6, 2, 2), (8, 3, 2147483659)])
def test_drain_drain_undrain_the_oldest_and_never_two_in_a_pod(k, aggs, seed):
    g = fat_tree_drained.build(k, aggs, 1, 0)
    standing_pods = {
        fat_tree_drained.pod_of_agg(g, n)
        for n in g.meta["drained"] if n >= g.meta["n_core"]}
    events = decision_fib_drain.drain_sequence(
        g, np.random.default_rng(seed), {"max_drained": 2})
    held: list[int] = []
    kinds = []
    for _ in range(200):
        node, bit = next(events)
        kinds.append(bit)
        if bit:
            assert node in g.meta["drain_pool"] and node not in held
            pod = fat_tree_drained.pod_of_agg(g, node)
            assert pod != 0 and pod not in standing_pods
            assert pod not in {fat_tree_drained.pod_of_agg(g, n) for n in held}
            held.append(node)
        else:
            assert node == held.pop(0), "the oldest is undrained"
        assert len(held) <= 2
    assert kinds[:5] == [True, True, False, True, False]
    # the same seed draws the same switches
    again = decision_fib_drain.drain_sequence(
        g, np.random.default_rng(seed), {"max_drained": 2})
    first = decision_fib_drain.drain_sequence(
        g, np.random.default_rng(seed), {"max_drained": 2})
    assert [next(again) for _ in range(20)] == [next(first) for _ in range(20)]


def test_a_kept_graph_holds_its_own_copy_of_the_drained_set():
    g = fat_tree_drained.build(4, 1, 1, 0)
    drained = set(g.meta["drained"])
    kept = fat_tree_drained.as_published(g, drained)
    drained.add(7)
    assert kept.meta["drained"] == g.meta["drained"] and 7 not in kept.meta["drained"]
    assert kept.meta is not g.meta and kept.meta["drain_pool"] is g.meta["drain_pool"]
    assert isinstance(kept, topo.Graph) and kept.metric is not g.metric


# ---------------------------------------------------------------------- faults


def test_a_sound_run_is_correct_and_every_event_is_a_structural_rebuild(checkout):
    line = run_in_process(checkout)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 < line["attempted"]
    assert line["compared"]["tables_missing"]["value"] == 0


def standing_set() -> frozenset:
    """The twin's standing set, as node names."""
    config = json.loads(
        (REPO / "perfbench" / "configs" / "tiny_fabric_drain.json").read_text())
    return frozenset(
        topo.node_name(n) for n in topo.build(config["topology"]).meta["drained"])


def the_standing_bits_ignored_by_the_solver(monkeypatch):
    """The CSR the solver works on says that no switch of the standing
    set is overloaded: they carry transit, as the reference's control has
    it, from the first RIB on. (With every bit ignored no event would move
    a route, and an event that tells Fib nothing only times out.)"""
    from openr_tpu.decision.linkstate import LinkState

    real, standing = LinkState.to_csr, standing_set()

    def standing_not_overloaded(self):
        csr = real(self)
        over = csr.node_overloaded.copy()
        over[[csr.name_to_id[n] for n in standing]] = False
        return dataclasses.replace(csr, node_overloaded=over)

    monkeypatch.setattr(LinkState, "to_csr", standing_not_overloaded)


def a_drained_switch_is_no_destination(monkeypatch):
    """The solver hands back no route to a switch that is drained, as if
    it were down: it has to stay reachable as a destination."""
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    real = TpuSpfSolver.compute_routes

    def without_the_drained(self, ls, ps, my_node, **kw):
        res = real(self, ls, ps, my_node, **kw)
        rdb = res[0] if isinstance(res, tuple) else res
        gone = [int(n.rpartition("-")[2]) for n in ls.nodes
                if ls.is_node_overloaded(n)]
        loopbacks = {topo.loopback(i) for i in gone}
        for prefix in list(rdb.unicast_routes):
            if str(prefix.prefix) in loopbacks:
                del rdb.unicast_routes[prefix]
        for i in gone:
            rdb.mpls_routes.pop(topo.node_label(i), None)
        return res

    monkeypatch.setattr(TpuSpfSolver, "compute_routes", without_the_drained)


def nothing_programmed_after_the_first_event(monkeypatch):
    """The handler acknowledges every update and applies the first alone
    (the first warm-up event's; the first RIB comes by `sync_fib`): its
    table keeps that one switch drained whatever was drained since."""
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
    the_standing_bits_ignored_by_the_solver, a_drained_switch_is_no_destination,
    nothing_programmed_after_the_first_event])
def test_a_planted_fault_reads_not_correct(checkout, monkeypatch, fault):
    fault(monkeypatch)
    line = run_in_process(checkout, seconds=2.0)
    assert line["correct"] is False
    # a drain moves a ToR's loopback and its label alike
    assert line["compared"]["unicast_routes_differ"]["value"] > 0
    assert line["compared"]["mpls_routes_differ"]["value"] > 0


def test_a_check_that_shares_the_drained_set_with_a_later_state_is_caught(
        checkout, monkeypatch):
    """`Graph.copy()` shares `meta`: were the kept graphs to share the
    live drained set, every table would be compared with the last state,
    and the tables taken earlier differ from it."""
    live: dict[int, set] = {}

    def shared(g, drained):
        live[id(g)] = drained
        kept = g.copy()
        kept.meta["drained"] = drained  # the set the driver goes on changing
        return kept

    monkeypatch.setattr(fat_tree_drained, "as_published", shared)
    line = run_in_process(checkout, seconds=2.0)
    assert live, "the driver took its kept graphs from as_published"
    assert line["correct"] is False
    assert line["compared"]["unicast_routes_differ"]["value"] > 0


def test_an_event_that_never_reaches_fib_programmed_fails_the_run(
        checkout, monkeypatch):
    traffic = checkout / "perfbench" / "traffic" / "agg_drain_undrain.json"
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
