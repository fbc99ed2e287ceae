"""The cell `backbone_lfa.circuit_cost_out` and its per-layer
metrics: where it is listed, and what its twin's run gives each metric.
Six entries list it alone and read what the deployment added to the
program: the spans `spf:lfa_backups` and `spf:lfa_dist_fetch`, the
counters `decision.spf.lfa_routes` and `.lfa_nexthops`, and the cold
kernel with its LFA bits in the trace with its roofline. The listing
check takes the benchmark as a dict, as the other listing checks do."""

import json
import time

import pytest
from perfbench_util import REPO, TINY_CELLS, load_benchmark, tiny_checkout

from perfbench import run, topo, work
from perfbench.events import root_of

CELL = "backbone_lfa.circuit_cost_out"
TWIN = "tiny_backbone_lfa.circuit_cost_out_lfa"
OWN = {
    "lfa_backups_ms", "lfa_dist_fetch_ms", "lfa_routes_per_event",
    "lfa_nexthops_per_event", "lfa_cold_kernel_ms", "lfa_cold_roofline",
}
#: the older entries its path passes through
LISTED = {
    "event_to_fib_p50_ms", "queue_in_ms", "debounce_wait_ms",
    "debounce_hold_ms", "decision_apply_ms", "prefix_snapshot_ms",
    "compute_rib_ms", "fetch_bytes_per_event", "rib_diff_ms",
    "export_counters_ms", "fib_program_ms", "fib_path_ms",
    "fib_routes_per_event", "prefix_snapshots_shared_per_event",
    "no_change_rebuilds_per_event", "warm_fallbacks_per_event",
    "area_solves_per_event", "cached_areas_per_event",
    "gc_pause_ms_per_event", "gc_full_collections", "gc_in_rebuild_ms",
    "thread_start_ms", "thread_return_ms", "window_compiles.backbone_ksp",
    "idle_named_share.backbone_ksp",
}
#: the drain cell's own (test_perfbench_drain_metrics.py)
DRAIN_OWN = {
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


def check_the_lfa_cells_listing(bench: dict) -> None:
    """The cell comes after the cells that were there, on one chip, and
    after them wherever it is listed; under exactly the older entries its
    path passes through and its own six, which list it alone; under no
    warm-start metric (it has no warm solve), not under
    `compute_rib_unattributed_ms`, not under the drain's own."""
    cells = [w["name"] for w in bench["workloads"]]
    older = cells[:cells.index(CELL)]
    assert {"backbone_ksp.circuit_cost_out",
            "fabric_drain.agg_drain_undrain"} <= set(older)
    mine = bench["workloads"][cells.index(CELL)]
    assert (mine["config"], mine["chips"]) == ("backbone_lfa", 1)
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
    assert not [n for n in listed if "warm" in n and n != "warm_fallbacks_per_event"]
    assert "compute_rib_unattributed_ms" not in listed
    assert not DRAIN_OWN & set(listed)


def test_the_cell_is_listed_where_its_path_passes():
    assert TINY_CELLS[TWIN] == CELL
    check_the_lfa_cells_listing(load_benchmark())


@pytest.fixture(scope="module")
def observed(tmp_path_factory):
    """One untraced run of the twin in this process: what the driver
    handed the harness."""
    checkout = tiny_checkout(tmp_path_factory.mktemp("perfbench_lfa_metrics"))
    seen = {}
    real = run.check_tables

    def keep(obs, reference):
        seen.update(obs)
        return real(obs, reference)

    run.check_tables = keep
    try:
        rc, line = run.run_cell(
            checkout, TWIN, 2147483831, 1.5, False, time.perf_counter())
    finally:
        run.check_tables = real
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    return seen


def test_the_twin_gives_every_host_metric_of_the_cell_a_number(observed):
    listed = {m["name"] for m in load_benchmark()["per_layer"]
              if CELL in m["workloads"] and m["source"] != "device_trace"}
    got = {name: read(name, observed) for name in listed}
    assert all(type(v) is float for v in got.values()), got
    # every event a cold solve of its area; the other area cached
    assert got["warm_fallbacks_per_event"] == 1.0
    assert got["area_solves_per_event"] == got["cached_areas_per_event"] == 1.0
    assert got["window_compiles.backbone_ksp"] == 0.0
    assert 0 < got["lfa_dist_fetch_ms"] < got["lfa_backups_ms"] < got["compute_rib_ms"]
    # the twin: an area routes 15 loopbacks (16 routers less the root),
    # most of them protected whatever the circuits costed out
    counters = observed["counters"]
    assert counters["decision.spf.lfa_routes"] + counters[
        "decision.spf.lfa_unprotected"] == 15 * observed["events"]
    assert 10.0 <= got["lfa_routes_per_event"] <= 15.0
    assert got["lfa_nexthops_per_event"] >= got["lfa_routes_per_event"]
    assert counters.get("decision.spf.engine_native", 0) == 0


def test_the_kept_tables_hold_the_backups_beside_the_routes(observed):
    """Each kept table: the handler's 29 routes and a row of backups for
    each protected one, a row under its route's key."""
    from perfbench import compare
    from perfbench.references.backbone_lfa import backup_key

    for chk in observed["checks"]:
        table = compare.plain_unicast(chk["unicast"])
        loopbacks = {k for k in table if not k.endswith(" backups")}
        rows = set(table) - loopbacks
        assert len(loopbacks) == 29 and len(rows) >= 20
        assert rows <= {backup_key(k) for k in loopbacks}


def test_a_program_without_the_spans_or_counters_gives_no_reading():
    older = {
        "series": {"latency_ms": [1.0, 2.0],
                   "decision.spf:unicast_general_ms": [3.0, 3.1]},
        "counters": {"decision.spf.warm_fallbacks": 2},
        "events": 2, "window_s": 1.0,
    }
    for name in OWN:
        assert read(name, older) is None, name


def test_the_roofline_reads_the_drivers_count_of_one_area_s_solve(observed):
    from openr_tpu.ops.spf import pad_batch

    config = json.loads(
        (REPO / "perfbench" / "configs" / "tiny_backbone_lfa.json").read_text())
    g = topo.build(config["topology"])
    root = root_of(g, config["root"])
    area = g.meta["edge_area"]
    w = observed["work"]
    assert w["nodes"] == 16 and w["edges"] == int((area == 0).sum())
    assert w["batch"] == int(pad_batch(1 + 4))
    assert w["out_bytes"] == (
        observed["counters"]["meter.fetched_bytes"] / observed["events"])
    assert int((g.src == root).sum()) == 8  # four neighbours in each area
    kernel_s = 0.004
    traced = {**observed, "traced_events": 5, "device_kind": "TPU v5 lite",
              "trace": {"kernels_s": {"batched_sssp_split_rib": 5 * kernel_s}}}
    assert read("lfa_cold_kernel_ms", traced) == pytest.approx(4.0)
    share = read("lfa_cold_roofline", traced)
    assert share == pytest.approx(work.roofline_share_pct(
        "TPU v5 lite", w["nodes"], w["edges"], w["batch"], w["out_bytes"],
        kernel_s))
    assert 0.0 < share < 100.0
