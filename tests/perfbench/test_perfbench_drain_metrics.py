"""The per-layer metrics of `fabric_drain.agg_drain_undrain` (PR 38). The
cell adds no per-layer entry of its own: it is listed under older metrics
whose readers find this driver's series and counters, and its twin gives
each of them a number. The spans and counters PR 38 added to the program
(`spf:table_build`, `spf:upload`, `decision.dev_cache.upload_bytes`,
`decision.rebuild.structural`) have no metric yet (PERF.md section 7 says
why); the driver records them all the same, and that is held here."""

import json
import time

import pytest
from perfbench_util import REPO, TINY_CELLS, load_benchmark, tiny_checkout
from test_perfbench_gc_spans import NEW as PR36

from perfbench import run

CELL = "fabric_drain.agg_drain_undrain"
TWIN = "tiny_fabric_drain.agg_drain_undrain"


def read(name: str, obs: dict):
    spec = json.loads(
        (REPO / "perfbench" / "layer_metrics" / f"{name}.json").read_text())
    return run.module("readers", spec["reader"]).read(obs, spec["args"])


@pytest.fixture(scope="module")
def observed(tmp_path_factory):
    """One untraced run of the twin in this process: what the driver
    handed the harness."""
    checkout = tiny_checkout(tmp_path_factory.mktemp("perfbench_drain_metrics"))
    seen = {}
    real = run.check_tables

    def keep(obs, reference):
        seen.update(obs)
        return real(obs, reference)

    run.check_tables = keep
    try:
        rc, line = run.run_cell(
            checkout, TWIN, 2147483789, 1.5, False, time.perf_counter())
    finally:
        run.check_tables = real
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    return seen


def test_the_cell_comes_after_what_was_there_wherever_it_is_listed():
    bench = load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert TINY_CELLS[TWIN] == CELL and CELL in cells
    older = cells[:cells.index(CELL)]
    assert {"fabric10k.metric_flap", "fabric_vips.tor_uplink_flap"} <= set(older)
    mine = bench["workloads"][cells.index(CELL)]
    assert (mine["config"], mine["chips"]) == ("fabric_drain", 1)
    listed = [m for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert "event_to_fib_p50_ms" in {m["name"] for m in listed}
    for m in listed:
        before = m["workloads"][:m["workloads"].index(CELL)]
        assert before and set(before) <= set(older), m["name"]
        assert m.get("moves", "event_to_fib_p50_ms") == "event_to_fib_p50_ms"
    names = {m["name"] for m in listed}
    # a structural event has no warm solve; PR 36's entries are held to
    # their four cells by test_perfbench_gc_spans.py
    assert not [n for n in names if "warm" in n]
    assert "compute_rib_unattributed_ms" not in names
    assert not names & PR36


def test_the_twin_gives_every_host_metric_of_the_cell_a_number(observed):
    listed = {m["name"] for m in load_benchmark()["per_layer"]
              if CELL in m["workloads"] and m["source"] != "device_trace"}
    got = {name: read(name, observed) for name in listed}
    assert all(type(v) is float for v in got.values()), got
    assert got["fib_routes_per_event"] == 4.0  # two ToRs: loopback and label
    assert got["no_change_rebuilds_per_event"] == 0.0
    assert 0 < got["to_csr_ms"] < got["compute_rib_ms"]
    assert got["rib_diff_ms"] > 0 and got["fib_path_ms"] > 0
    # under the VIPs' name: no entry of the cell's own can be appended
    assert got["window_compiles.fabric_vips"] == 0.0


def test_the_driver_records_the_structural_paths_spans_and_counters(observed):
    events = observed["events"]
    series, counters = observed["series"], observed["counters"]
    for name in ("spf:table_build", "spf:upload", "spf:dispatch", "spf:prepare",
                 "spf:batched_solve", "spf:rib_election"):
        assert len(series[f"decision.{name}_ms"]) == events, name
    for build, upload, dispatch in zip(
            series["decision.spf:table_build_ms"],
            series["decision.spf:upload_ms"],
            series["decision.spf:dispatch_ms"]):
        assert 0 < build and 0 < upload and build + upload <= dispatch
    # every event is one structural rebuild on the device engine, each
    # placing the tables of a new topology base
    assert counters["decision.rebuild.structural"] == events
    assert counters["decision.rebuild.full"] == events
    assert counters["decision.dev_cache.uploads"] == events
    assert counters["decision.dev_cache.upload_bytes"] > 0
    assert counters["decision.dev_cache.upload_bytes"] % events == 0
    assert counters.get("decision.spf.engine_native", 0) == 0
    assert counters["meter.compiles"] == counters["meter.backend_compiles"] == 0
