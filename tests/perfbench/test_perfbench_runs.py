"""The harness end to end on the CPU, on the rehearsal configurations: the
contract's last line, the refusals, cells added as files, and the faults
that have to make `correct` come out false."""

import json
import shutil

import pytest
from perfbench_util import (
    REPO,
    TINY_CELLS,
    last_line,
    load_benchmark,
    run_py,
    tiny_checkout,
    write_benchmark,
)

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("perfbench"))


def metrics_of(bench, cell, section):
    return {m["name"] for m in bench[section]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_untraced_run_ends_in_the_contracts_line(checkout, cell):
    proc = run_py(checkout, "--workload", cell, "--seed", "2147483659",
                  "--seconds", "2", "--trace", "0")
    line = last_line(proc)
    assert set(line) == LINE_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"  # never mistakable for a chip
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    assert set(line["metrics"]) == metrics_of(bench, cell, "end_to_end")
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert m["value"] > 0
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    # the numbers compared are the last lines on stderr, each with its limit
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(ln.startswith("compared ") and "(limit " in ln for ln in tail)


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_traced_run_reports_per_layer_metrics_and_the_device_window(checkout, cell):
    line = last_line(run_py(checkout, "--workload", cell, "--seed", "7",
                            "--seconds", "5", "--trace", "1"))
    assert set(line) == LINE_KEYS | {"breakdown"} and list(line)[-1] == "compared"
    assert line["correct"] is True
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    # a reader that finds nothing to read (no device plane on the CPU)
    # leaves its metric out; no end-to-end metric is in a traced line
    assert set(line["metrics"]) <= metrics_of(bench, cell, "per_layer")
    assert not any("kernel" in n or "roofline" in n for n in line["metrics"])
    assert any(n.startswith("window_compiles") for n in line["metrics"])
    assert all(m["value"] == 0 for n, m in line["metrics"].items()
               if n.startswith("window_compiles"))


def test_a_real_configuration_is_refused_off_the_chip():
    cell = load_benchmark()["workloads"][0]["name"]
    proc = run_py(REPO, "--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert proc.returncode == 1 and proc.stdout.strip() == ""
    assert "platform 'cpu'" in proc.stderr and "not a TPU" in proc.stderr


def test_alone_without_the_program_it_refuses(tmp_path):
    root = tiny_checkout(tmp_path, with_program=False)
    proc = run_py(root, "--workload", sorted(TINY_CELLS)[0], "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_an_unknown_cell_is_refused(checkout):
    proc = run_py(checkout, "--workload", "nowhere.nothing", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert proc.returncode == 1 and proc.stdout.strip() == ""


def test_a_cell_a_configuration_a_mix_and_a_metric_added_as_files(tmp_path):
    """What a later PR does: new files and one entry each, no edit."""
    root = tiny_checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    pb = root / "perfbench"
    config = json.loads((pb / "configs" / "tiny_er.json").read_text())
    config["name"] = "tiny_er_sparse"
    config["topology"]["avg_degree"] = 4
    (pb / "configs" / "tiny_er_sparse.json").write_text(json.dumps(config))
    traffic = json.loads((pb / "traffic" / "full_rib.json").read_text())
    traffic["metric_range"] = [1, 8]
    traffic["end_to_end"] = {"rebuild_p50_ms": {"series": "latency_ms", "stat": "p50"}}
    (pb / "traffic" / "narrow_metrics.json").write_text(json.dumps(traffic))
    (pb / "readers" / "events_counted.py").write_text(
        "def read(obs, args):\n    return float(obs['events'])\n")
    (pb / "layer_metrics" / "rebuilds_in_window.json").write_text(json.dumps({
        "name": "rebuilds_in_window", "layer": "harness", "moves": "rebuild_p50_ms",
        "reader": "events_counted"}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny_er_sparse.narrow_metrics"
    bench["configs"].append({
        "name": "tiny_er_sparse", "source": "test",
        "file": "perfbench/configs/tiny_er_sparse.json", "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": cell, "config": "tiny_er_sparse", "traffic": "narrow_metrics",
        "chips": 1, "why": "test"})
    bench["end_to_end"].append({
        "name": "rebuild_p50_ms", "unit": "ms", "better": "lower", "bound": 0.05,
        "source": "host_clock", "workloads": [cell]})
    bench["per_layer"].append({
        "name": "rebuilds_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "harness", "moves": "rebuild_p50_ms",
        "workloads": [cell]})
    write_benchmark(root, bench)
    line = last_line(run_py(root, "--workload", cell, "--seed", "3",
                            "--seconds", "1", "--trace", "0"))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"rebuild_p50_ms", "setup_s"}
    line = last_line(run_py(root, "--workload", cell, "--seed", "3",
                            "--seconds", "5", "--trace", "1"))
    assert line["correct"] is True
    assert line["metrics"]["rebuilds_in_window"]["value"] == line["attempted"]
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"
    shutil.rmtree(root)


# ---------------------------------------------------------------------- faults


def run_in_process(checkout, cell, seed=11, seconds=1.0):
    import time

    from perfbench import run

    rc, line = run.run_cell(checkout, cell, seed, seconds, False, time.perf_counter())
    assert rc == 0
    return line


def test_a_sound_run_in_process_is_correct(checkout):
    for cell in sorted(TINY_CELLS):
        line = run_in_process(checkout, cell)
        assert line["correct"] is True, line["compared"]


def fault_state_unchanged_fabric(monkeypatch):
    """The handler acknowledges every route update and keeps its table."""
    from openr_tpu.fib import MockFibHandler

    real = MockFibHandler.add_unicast_routes
    calls = {"n": 0}

    async def stale(self, client_id, routes):
        calls["n"] += 1
        if calls["n"] <= 1:  # the first RIB goes in; nothing after it
            await real(self, client_id, routes)

    monkeypatch.setattr(MockFibHandler, "add_unicast_routes", stale)


def fault_answer_altered_fabric(monkeypatch):
    """One next hop is dropped from every ECMP route as it is programmed."""
    import dataclasses

    from openr_tpu.fib import MockFibHandler

    real = MockFibHandler.add_unicast_routes

    async def altered(self, client_id, routes):
        await real(self, client_id, [
            dataclasses.replace(r, nexthops=tuple(r.nexthops)[:1])
            if len(r.nexthops) > 1 else r for r in routes])

    monkeypatch.setattr(MockFibHandler, "add_unicast_routes", altered)


def fault_state_unchanged_er(monkeypatch):
    """compute_routes returns the first RouteDatabase it ever made."""
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    real = TpuSpfSolver.compute_routes
    first = {}

    def stale(self, ls, ps, my_node, **kw):
        rdb = real(self, ls, ps, my_node, **kw)
        return first.setdefault("rdb", rdb)

    monkeypatch.setattr(TpuSpfSolver, "compute_routes", stale)


def fault_answer_altered_er(monkeypatch):
    """One MPLS route loses its next hops where it is produced."""
    import dataclasses

    from openr_tpu.decision.spf_backend import TpuSpfSolver

    real = TpuSpfSolver.compute_routes

    def altered(self, ls, ps, my_node, **kw):
        rdb = real(self, ls, ps, my_node, **kw)
        label = next(iter(rdb.mpls_routes))
        rdb.mpls_routes[label] = dataclasses.replace(
            rdb.mpls_routes[label], nexthops=())
        return rdb

    monkeypatch.setattr(TpuSpfSolver, "compute_routes", altered)


@pytest.mark.parametrize("cell,fault,numbers", [
    ("tiny_fabric.metric_flap", fault_state_unchanged_fabric, ["unicast_routes_differ"]),
    ("tiny_fabric.metric_flap", fault_answer_altered_fabric, ["unicast_routes_differ"]),
    ("tiny_er.full_rib", fault_state_unchanged_er, ["unicast_routes_differ", "mpls_routes_differ"]),
    ("tiny_er.full_rib", fault_answer_altered_er, ["mpls_routes_differ"]),
], ids=["fabric-state-unchanged", "fabric-answer-altered",
        "er-state-unchanged", "er-answer-altered"])
def test_a_fault_under_the_timed_path_makes_correct_false(
        checkout, monkeypatch, cell, fault, numbers):
    fault(monkeypatch)
    line = run_in_process(checkout, cell)
    assert line["correct"] is False
    for name in numbers:
        assert line["compared"][name]["value"] > line["compared"][name]["limit"]


def test_an_event_that_never_reaches_fib_programmed_fails_the_run(checkout, monkeypatch):
    import json as _json

    traffic = checkout / "perfbench" / "traffic" / "metric_flap.json"
    saved = traffic.read_text()
    spec = _json.loads(saved)
    spec["event_timeout_s"] = 1.0
    from openr_tpu.fib import Fib

    real = Fib._complete_traces
    calls = {"n": 0}

    def lose_traces(self, n_covered):
        calls["n"] += 1
        if calls["n"] > 30:
            self._pending_perf.clear()
            return None
        return real(self, n_covered)

    monkeypatch.setattr(Fib, "_complete_traces", lose_traces)
    try:
        traffic.write_text(_json.dumps(spec))
        line = run_in_process(checkout, "tiny_fabric.metric_flap", seconds=3.0)
    finally:
        traffic.write_text(saved)
    assert line["failed"] >= 1 and line["correct"] is False
    assert line["compared"]["events_failed"]["value"] >= 1
