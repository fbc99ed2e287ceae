"""The planted faults of the driver `decision_fib_vips` (PR 34), in a file
of its own as perfbench/README.md asks of a new driver: what has to make
the rehearsal cell of `fabric_vips.tor_uplink_flap` read `correct:
false`, in-process on the CPU."""

import dataclasses
import json
import time

import pytest
from perfbench_util import lose_traces_in_the_window, tiny_checkout

CELL = "tiny_fabric_vips.tor_uplink_flap"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("perfbench_vips"))


def run_in_process(checkout, seed=11, seconds=1.0):
    from perfbench import run

    rc, line = run.run_cell(checkout, CELL, seed, seconds, False, time.perf_counter())
    assert rc == 0
    return line


def test_a_sound_run_is_correct_and_puts_the_loopback_builder_back(checkout):
    from perfbench.drivers import decision_fib, decision_fib_vips

    line = run_in_process(checkout)
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["tables_missing"]["value"] == 0
    assert decision_fib.program_dbs is decision_fib_vips.loopback_dbs


def weights_lost_at_the_handler(monkeypatch):
    """Every next hop is programmed with weight 0: UCMP as plain ECMP."""
    from openr_tpu.fib import MockFibHandler

    real = MockFibHandler.add_unicast_routes

    async def flat(self, client_id, routes):
        await real(self, client_id, [
            dataclasses.replace(r, nexthops=tuple(
                dataclasses.replace(nh, weight=0) for nh in r.nexthops))
            for r in routes])

    monkeypatch.setattr(MockFibHandler, "add_unicast_routes", flat)


def weights_not_normalised(monkeypatch):
    """The summed weights are programmed as they are, not divided by
    their gcd."""
    from openr_tpu.decision import spf_backend

    monkeypatch.setattr(spf_backend, "normalize_weights", lambda wsum: wsum)


def weights_ignored_by_the_election(monkeypatch):
    """No advertiser's weight is read: every VIP is elected as anycast
    ECMP."""
    from openr_tpu.decision import spf_backend

    monkeypatch.setattr(spf_backend, "ucmp_weights", lambda chosen: None)


def nothing_programmed_after_the_first_rib(monkeypatch):
    """The handler acknowledges every unicast update and applies the
    first alone (the first warm-up event's; the first RIB comes by
    `sync_fib`): the table keeps that event's link raised and no other,
    and the links raised when a table is compared are later draws."""
    from openr_tpu.fib import MockFibHandler

    real = MockFibHandler.add_unicast_routes
    calls = {"n": 0}

    async def stale(self, client_id, routes):
        calls["n"] += 1
        if calls["n"] <= 1:
            await real(self, client_id, routes)

    monkeypatch.setattr(MockFibHandler, "add_unicast_routes", stale)


@pytest.mark.parametrize("fault", [
    weights_lost_at_the_handler, weights_ignored_by_the_election,
    weights_not_normalised, nothing_programmed_after_the_first_rib])
def test_a_planted_fault_reads_not_correct(checkout, monkeypatch, fault):
    fault(monkeypatch)
    line = run_in_process(checkout, seconds=2.0)
    assert line["correct"] is False
    assert line["compared"]["unicast_routes_differ"]["value"] > 0
    # none of them touches a label route
    assert line["compared"]["mpls_routes_differ"]["value"] == 0


def test_an_event_that_never_reaches_fib_programmed_fails_the_run(
        checkout, monkeypatch):
    traffic = checkout / "perfbench" / "traffic" / "tor_uplink_flap.json"
    saved = traffic.read_text()
    spec = json.loads(saved)
    spec["event_timeout_s"] = 1.0
    lose_traces_in_the_window(monkeypatch)
    try:
        traffic.write_text(json.dumps(spec))
        line = run_in_process(checkout, seconds=3.0)
    finally:
        traffic.write_text(saved)
    assert line["failed"] >= 1 and line["correct"] is False
    assert line["compared"]["events_failed"]["value"] >= 1
