"""The per-layer metrics of `fabric_drain.agg_drain_undrain` (PR 38, PR 40).
The cell is listed under older metrics whose readers find this driver's
series and counters, and, since PR 40, under twelve entries of its own that
read its structural path: the spans and counters PR 38 and PR 39 added to
the program (`spf:table_build`, `spf:upload`,
`decision.dev_cache.upload_bytes`, `decision.rebuild.structural`,
`decision.csr.builds`, `decision.csr.overload_rebases`) and the cold
phases of an event (prepare, solve, assembly, what no span names, the
kernel and its roofline). Its twin gives each host-side metric a number.
The listing check takes the benchmark as a dict, so that
test_perfbench_contract.py can run it on a benchmark with one entry and one
cell more."""

import json
import time

import pytest
from perfbench_util import REPO, TINY_CELLS, load_benchmark, tiny_checkout

from perfbench import run, topo, work
from perfbench.events import root_of

CELL = "fabric_drain.agg_drain_undrain"
TWIN = "tiny_fabric_drain.agg_drain_undrain"
#: its own entries (PR 40): the structural path's spans and counters
OWN = {
    "table_build_ms", "table_upload_ms", "upload_bytes_per_event",
    "structural_rebuilds_per_event", "csr_builds_per_event",
    "csr_overload_rebases_per_event", "event_cold_prepare_ms",
    "event_cold_solve_wall_ms", "event_assemble_ms",
    "event_full_unattributed_ms", "event_cold_kernel_ms", "event_cold_roofline",
}


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


def check_the_drain_cells_listing(bench: dict) -> None:
    """Wherever the cell is listed beside other cells, it comes after them;
    every metric it is listed under moves its p50; a structural event has
    no warm solve, so no warm-path metric and not
    `compute_rib_unattributed_ms` (the residual of the warm spans:
    `event_full_unattributed_ms` is the cold path's); its own entries list
    it alone."""
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    older = cells[:cells.index(CELL)]
    assert {"fabric10k.metric_flap", "fabric_vips.tor_uplink_flap"} <= set(older)
    mine = bench["workloads"][cells.index(CELL)]
    assert (mine["config"], mine["chips"]) == ("fabric_drain", 1)
    listed = [m for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert "event_to_fib_p50_ms" in {m["name"] for m in listed}
    for m in listed:
        before = m["workloads"][:m["workloads"].index(CELL)]
        assert set(before) <= set(older), m["name"]
        assert before or m["name"] in OWN, m["name"]
        assert m.get("moves", "event_to_fib_p50_ms") == "event_to_fib_p50_ms"
    names = {m["name"] for m in listed}
    assert OWN <= names
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL], m["name"]
    assert not [n for n in names if "warm" in n]
    assert "compute_rib_unattributed_ms" not in names


def test_the_cell_comes_after_what_was_there_wherever_it_is_listed():
    assert TINY_CELLS[TWIN] == CELL
    check_the_drain_cells_listing(load_benchmark())


def test_the_twin_gives_every_host_metric_of_the_cell_a_number(observed):
    listed = {m["name"] for m in load_benchmark()["per_layer"]
              if CELL in m["workloads"] and m["source"] != "device_trace"}
    got = {name: read(name, observed) for name in listed}
    assert all(type(v) is float for v in got.values()), got
    assert got["fib_routes_per_event"] == 4.0  # two ToRs: loopback and label
    assert got["no_change_rebuilds_per_event"] == 0.0
    assert 0 < got["to_csr_ms"] < got["compute_rib_ms"]
    assert got["rib_diff_ms"] > 0 and got["fib_path_ms"] > 0
    # under the VIPs' name (same reader, same graph): the cell keeps it
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


def test_the_twin_reads_the_structural_path(observed):
    """Every event is one structural rebuild whose base is the last one
    re-based with one overload bit flipped, never built, and whose tables
    are placed anew; the cold phases split `compute_rib_ms`."""
    got = {name: read(name, observed) for name in OWN}
    assert got["structural_rebuilds_per_event"] == 1.0
    assert got["csr_overload_rebases_per_event"] == 1.0
    assert got["csr_builds_per_event"] == 0.0
    counters = observed["counters"]
    per_base = (counters["decision.dev_cache.upload_bytes"]
                / counters["decision.dev_cache.uploads"])
    assert got["upload_bytes_per_event"] == per_base > 0
    assert 0 < got["table_build_ms"] + got["table_upload_ms"] \
        <= got["event_cold_prepare_ms"]
    compute_rib = read("compute_rib_ms", observed)
    assert -0.05 < got["event_full_unattributed_ms"] < compute_rib
    assert got["event_cold_solve_wall_ms"] > 0 and got["event_assemble_ms"] > 0
    # the CPU's trace has no device plane: the kernel and its roofline are
    # left out of the line, not read as 0 (test_perfbench_runs.py)
    assert got["event_cold_kernel_ms"] is None
    assert got["event_cold_roofline"] is None


def test_the_roofline_reads_the_drivers_count_of_the_least_work(observed):
    """The driver's `work` is the whole graph's solve at the root's padded
    batch, as `solver_rebuild` counts it, and the roofline's reader turns
    a kernel time from the trace into a share of it."""
    from openr_tpu.ops.spf import pad_batch

    config = json.loads(
        (REPO / "perfbench" / "configs" / "tiny_fabric_drain.json").read_text())
    g = topo.build(config["topology"])
    root = root_of(g, config["root"])
    w = observed["work"]
    assert w == {
        "nodes": g.n, "edges": g.num_edges,
        "batch": int(pad_batch(1 + int((g.src == root).sum()))),
        "out_bytes": observed["counters"]["meter.fetched_bytes"]
        / observed["events"],
    }
    kernel_s = 0.002  # a traced event's kernel, as the trace would give it
    traced = {**observed, "traced_events": 4, "device_kind": "TPU v5 lite",
              "trace": {"kernels_s": {"batched_sssp_split_rib": 4 * kernel_s}}}
    assert read("event_cold_kernel_ms", traced) == pytest.approx(2.0)
    assert read("event_cold_roofline", traced) == pytest.approx(
        work.roofline_share_pct("TPU v5 lite", w["nodes"], w["edges"],
                                w["batch"], w["out_bytes"], kernel_s))
