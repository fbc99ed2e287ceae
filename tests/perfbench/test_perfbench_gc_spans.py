"""The per-layer metrics of PR 36: the garbage collector's pauses, the
merge fold, the parts of the warm reassembly and the solver thread's
hand-off. Every twin's run gives each of its cell's new metrics a number;
a program without the spans and counters (the parent) gives none, not 0;
a full collection planted inside a rebuild shows in all three readings."""

import gc
import json
import time

import pytest
from perfbench_util import REPO, TINY_CELLS, load_benchmark, tiny_checkout

from perfbench import run

NEW = {
    "gc_pause_ms_per_event", "gc_full_collections", "gc_in_rebuild_ms",
    "gc_pause_ms_per_rebuild", "gc_full_collections.er100k",
    "merge_fold_ms", "warm_scope_ms", "warm_table_copy_ms", "warm_labels_ms",
    "warm_reassemble_unattributed_ms", "thread_start_ms", "thread_return_ms",
}
EVENT_CELLS = {
    "fabric10k.metric_flap", "lsdb100k.cost_out_in",
    "backbone_ksp.circuit_cost_out", "fabric_vips.tor_uplink_flap",
}


def spec_of(name: str) -> dict:
    return json.loads(
        (REPO / "perfbench" / "layer_metrics" / f"{name}.json").read_text())


def new_metrics_of(real_cell: str) -> list[dict]:
    return [m for m in load_benchmark()["per_layer"]
            if m["name"] in NEW and real_cell in m["workloads"]]


def read(name: str, obs: dict):
    spec = spec_of(name)
    return run.module("readers", spec["reader"]).read(obs, spec["args"])


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("perfbench_gc"))


def observed(checkout, cell, monkeypatch, seconds=1.0) -> dict:
    """One untraced run of `cell` in this process; what the driver handed
    the harness (series, counters) and the window's events and length,
    as the readers of a traced run get them."""
    seen = {}
    real = run.check_tables

    def keep(obs, reference):
        seen.update(obs)
        return real(obs, reference)

    monkeypatch.setattr(run, "check_tables", keep)
    rc, line = run.run_cell(
        checkout, cell, 2147483789, seconds, False, time.perf_counter())
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    return seen


def test_the_twelve_entries_are_what_the_issue_lists():
    per_layer = {m["name"]: m for m in load_benchmark()["per_layer"]}
    assert NEW <= set(per_layer)
    assert list(per_layer)[-12:] == [
        m for m in per_layer if m in NEW], "appended, in one block, at the end"
    for name in NEW:
        m = per_layer[name]
        assert m["better"] == "lower"
        er = name in ("gc_pause_ms_per_rebuild", "gc_full_collections.er100k")
        assert set(m["workloads"]) == ({"er100k.full_rib"} if er else EVENT_CELLS)
        assert m["moves"] == ("full_rib_ms" if er else "event_to_fib_p50_ms")
        spec = spec_of(name)
        assert spec["reader"] in {
            "series_stat", "series_residual", "counter_per_event", "counter_sum"}
    assert {per_layer[n]["layer"] for n in NEW} == {
        "runtime (python gc)", "Decision publish", "election + assembly",
        "Decision queue + debounce",
    }


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_every_twin_gives_each_new_metric_of_its_cell_a_number(
    checkout, cell, monkeypatch,
):
    obs = observed(checkout, cell, monkeypatch)
    mine = new_metrics_of(TINY_CELLS[cell])
    assert len(mine) == (2 if TINY_CELLS[cell] == "er100k.full_rib" else 10)
    got = {m["name"]: read(m["name"], obs) for m in mine}
    assert all(type(v) is float for v in got.values()), got
    for name, value in got.items():
        if name == "warm_reassemble_unattributed_ms":
            whole = read("warm_reassemble_ms", obs)
            assert -0.05 < value < max(1.0, whole), (value, whole)
        elif name.startswith("gc_full_collections"):
            assert value >= 0 and value == int(value)
        elif name.startswith("thread_"):
            assert value > 0.0  # every rebuild crosses both ways
        else:
            assert value >= 0.0, name
    # the young generations run in any window of a few hundred rebuilds
    pause = got.get("gc_pause_ms_per_event", got.get("gc_pause_ms_per_rebuild"))
    assert pause > 0.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_span_or_counter_gives_no_reading(name):
    """The parent of PR 36 under these files: its drivers record neither
    the series nor the counters, and the metric is left out of the line,
    not printed as 0."""
    older = {
        "series": {"latency_ms": [1.0, 2.0],
                   "decision.spf:warm_reassemble_ms": [0.5, 0.6],
                   "decision.spf:unicast_general_ms": [0.1, 0.1],
                   "decision.spf:ksp_ms": [0.0, 0.0]},
        "counters": {"decision.spf.warm_starts": 2, "solver.dense_sweeps": 30},
        "events": 2, "window_s": 1.0,
    }
    assert read(name, older) is None


def test_a_full_collection_planted_in_the_fold_shows_in_all_three(
    checkout, monkeypatch,
):
    from openr_tpu.decision import decision

    real = decision.merge_scope_delta
    planted = []

    def fold_in_window(*args, **kwargs):
        if opened and len(planted) < 2:
            planted.append(gc.collect())  # inside decision:merge_scope
        return real(*args, **kwargs)

    opened = []
    real_open = run.Window.open

    def open_(self):
        opened.append(self)
        real_open(self)

    monkeypatch.setattr(decision, "merge_scope_delta", fold_in_window)
    monkeypatch.setattr(run.Window, "open", open_)
    obs = observed(checkout, "tiny_fabric.metric_flap", monkeypatch)
    assert len(planted) == 2
    assert read("gc_full_collections", obs) >= 2
    in_fold = [ms for ms in obs["series"]["decision.decision:gc_ms"] if ms > 0]
    assert len(in_fold) >= 2
    events = obs["events"]
    in_rebuild = read("gc_in_rebuild_ms", obs)
    assert in_rebuild * events == pytest.approx(
        sum(obs["series"]["decision.decision:gc_ms"])
        + sum(obs["series"]["decision.spf:gc_ms"]))
    # the totals hold the spans
    assert read("gc_pause_ms_per_event", obs) * events >= sum(in_fold) * 0.99


def test_a_full_collection_planted_in_the_assembly_shows_in_the_solver_s(
    checkout, monkeypatch,
):
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    real = TpuSpfSolver._assemble_routes
    calls = []

    def assemble(self, *args, **kwargs):
        calls.append(gc.collect())  # inside spf:rib_assembly
        return real(self, *args, **kwargs)

    monkeypatch.setattr(TpuSpfSolver, "_assemble_routes", assemble)
    obs = observed(checkout, "tiny_er.full_rib", monkeypatch)
    # one a rebuild (and the odd one the interpreter began by itself)
    assert obs["events"] <= read("gc_full_collections.er100k", obs) <= (
        obs["events"] + 2)
    assert read("gc_pause_ms_per_rebuild", obs) > 0.0
