"""The cell `fabric_linkfail.tor_uplink_down_up` and its per-layer
metrics: where it is listed, and what its twin's run gives each metric.
Six entries list it alone and read what the deployment added to the
program: the five spans inside `_build_csr` (four by name, the fifth in
the residual of `spf:to_csr`) and the counter
`decision.csr.adjacencies_walked`. The listing check takes the benchmark
as a dict, as the other listing checks do, and holds on a benchmark with
one entry and one cell more."""

import json
import time

import pytest
from perfbench_util import REPO, TINY_CELLS, load_benchmark, tiny_checkout
from test_perfbench_contract import with_one_entry_and_one_cell
from test_perfbench_drain_metrics import OWN as DRAIN_OWN

from perfbench import run

CELL = "fabric_linkfail.tor_uplink_down_up"
TWIN = "tiny_fabric_linkfail.tor_uplink_down_up"
OWN = {
    "csr_index_ms", "csr_walk_ms", "csr_collapse_ms", "csr_sort_ms",
    "csr_build_unattributed_ms", "csr_adjacencies_walked_per_event",
}
#: the older entries its path passes through
LISTED = {
    "event_to_fib_p50_ms", "queue_in_ms", "debounce_wait_ms",
    "debounce_hold_ms", "decision_apply_ms", "prefix_snapshot_ms",
    "prefix_snapshots_shared_per_event", "compute_rib_ms", "to_csr_ms",
    "fetch_bytes_per_event", "rib_diff_ms", "export_counters_ms",
    "fib_program_ms", "fib_path_ms", "fib_routes_per_event",
    "gc_pause_ms_per_event", "gc_full_collections", "gc_in_rebuild_ms",
    "thread_start_ms", "thread_return_ms", "window_compiles.fabric_vips",
    "idle_named_share.fabric_vips",
}
#: the build's spans, as the driver records them
SPANS = ["decision.spf:csr_index_ms", "decision.spf:csr_walk_ms",
         "decision.spf:csr_collapse_ms", "decision.spf:csr_sort_ms",
         "decision.spf:csr_masks_ms"]


def read(name: str, obs: dict):
    spec = json.loads(
        (REPO / "perfbench" / "layer_metrics" / f"{name}.json").read_text())
    return run.module("readers", spec["reader"]).read(obs, spec["args"])


def check_the_linkfail_cells_listing(bench: dict) -> None:
    """The cell comes after every cell that was there, on one chip, and
    after them wherever it is listed; under exactly the older entries its
    path passes through and its own six, which list it alone and move its
    p50; under no warm-path entry and no entry of the drain's own."""
    cells = [w["name"] for w in bench["workloads"]]
    older = cells[:cells.index(CELL)]
    assert {"fabric10k.metric_flap", "fabric_drain.agg_drain_undrain",
            "backbone_lfa.circuit_cost_out"} <= set(older)
    mine = bench["workloads"][cells.index(CELL)]
    assert (mine["config"], mine["traffic"], mine["chips"]) == (
        "fabric_linkfail", "tor_uplink_down_up", 1)
    listed = {}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            before = m["workloads"][:m["workloads"].index(CELL)]
            assert set(before) <= set(older), m["name"]
            listed[m["name"]] = m
    assert set(listed) == LISTED | OWN
    for name in OWN:
        m = listed[name]
        assert m["workloads"] == [CELL] and m["moves"] == "event_to_fib_p50_ms"
        assert m["layer"] == "SPF solve, host side" and m["better"] == "lower"
    assert not [n for n in listed if "warm" in n]
    assert "compute_rib_unattributed_ms" not in listed
    assert not DRAIN_OWN & set(listed)


def test_the_cell_comes_after_every_older_cell_wherever_it_is_listed():
    assert TINY_CELLS[TWIN] == CELL
    check_the_linkfail_cells_listing(load_benchmark())


def test_the_listing_holds_with_one_entry_and_one_cell_appended(tmp_path):
    """As the guard of test_perfbench_contract.py appends them for the
    next PR."""
    src = tmp_path / "src"
    src.mkdir()
    check_the_linkfail_cells_listing(with_one_entry_and_one_cell(src))


@pytest.fixture(scope="module")
def observed(tmp_path_factory):
    """One untraced run of the twin in this process: what the driver
    handed the harness."""
    checkout = tiny_checkout(tmp_path_factory.mktemp("perfbench_linkfail_metrics"))
    seen = {}
    real = run.check_tables

    def keep(obs, reference):
        seen.update(obs)
        return real(obs, reference)

    run.check_tables = keep
    try:
        rc, line = run.run_cell(
            checkout, TWIN, 2147483801, 1.5, False, time.perf_counter())
    finally:
        run.check_tables = real
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    return seen


def test_the_twin_gives_every_host_metric_of_the_cell_a_number(observed):
    listed = {m["name"] for m in load_benchmark()["per_layer"]
              if CELL in m["workloads"] and m["source"] != "device_trace"}
    got = {name: read(name, observed) for name in listed}
    assert all(type(v) is float for v in got.values()), got
    # one ToR's loopback and label an event
    assert got["fib_routes_per_event"] == 2.0
    assert got["window_compiles.fabric_vips"] == 0.0
    assert 0 < got["to_csr_ms"] < got["compute_rib_ms"]
    assert got["csr_walk_ms"] > 0 and got["csr_index_ms"] > 0
    assert got["csr_collapse_ms"] > 0 and got["csr_sort_ms"] > 0
    assert got["csr_build_unattributed_ms"] > 0  # the interning at least
    # the twin's 64 adjacencies less both ends of the 2 standing links
    # and of the 1 or 2 the traffic holds down
    assert 56.0 <= got["csr_adjacencies_walked_per_event"] <= 58.0


def test_every_event_is_one_build_whose_spans_lie_inside_to_csr(observed):
    events = observed["events"]
    series, counters = observed["series"], observed["counters"]
    assert counters["decision.csr.builds"] == events
    assert counters["decision.rebuild.structural"] == events
    assert counters.get("decision.csr.overload_rebases", 0) == 0
    assert counters.get("decision.spf.engine_native", 0) == 0
    assert counters["meter.compiles"] == counters["meter.backend_compiles"] == 0
    for name in SPANS + ["decision.spf:to_csr_ms"]:
        assert len(series[name]) == events, name
    for whole, *parts in zip(series["decision.spf:to_csr_ms"],
                             *(series[n] for n in SPANS)):
        assert all(p > 0 for p in parts) and sum(parts) <= whole
    # the walk counts the published LSDB, 2 adjacencies a link down
    walked = counters["decision.csr.adjacencies_walked"]
    assert walked % 2 == 0 and 56 * events <= walked <= 58 * events


def test_a_program_without_the_spans_or_counter_gives_no_reading():
    """The parent under these files: its drivers record neither the
    build's spans nor the counter, and the metric is left out of the line,
    not printed as 0."""
    older = {
        "series": {"latency_ms": [1.0, 2.0],
                   "decision.spf:to_csr_ms": [3.0, 3.1]},
        "counters": {"decision.csr.builds": 2},
        "events": 2, "window_s": 1.0,
    }
    for name in OWN:
        assert read(name, older) is None, name
