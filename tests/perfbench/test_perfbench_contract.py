"""BENCHMARK.json against the builder's contract, and the harness's own
rules: everything a cell needs is found by name, nothing needs an edit."""

import json
import re

import pytest
from perfbench_util import REPO, load_benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return load_benchmark()


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert type(bench["run_seconds"]) is int and 1 <= bench["run_seconds"] <= 51
    # the full check with 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_and_cells(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(configs) == len(bench["configs"])
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("perfbench/") and (REPO / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
        body = json.loads((REPO / c["file"]).read_text())
        assert body["name"] == c["name"] and not body.get("rehearsal")
        assert body["guarantees"], "a deployment states its guarantees"
    cells = bench["workloads"]
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        traffic = REPO / "perfbench" / "traffic" / f"{w['traffic']}.json"
        driver = json.loads(traffic.read_text())["driver"]
        assert (REPO / "perfbench" / "drivers" / f"{driver}.py").is_file()
    assert {w["config"] for w in cells} == set(configs), "every config has a cell"
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] == 0.25

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert reported_in(m) <= cells
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert one_line(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        # each of its cells reports the end-to-end metric it should move
        assert reported_in(m) <= reported_in(e2e[m["moves"]])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    for cell in cells:
        mine = [m for m in bench["end_to_end"] if cell in reported_in(m)]
        assert len(mine) >= 2, f"{cell}: setup_s and one other end-to-end metric"
        assert any(cell in reported_in(m) for m in bench["per_layer"])


def test_every_layer_metric_has_its_file_and_reader(bench):
    e2e_traffic = {}
    for w in bench["workloads"]:
        traffic = json.loads(
            (REPO / "perfbench" / "traffic" / f"{w['traffic']}.json").read_text())
        e2e_traffic[w["name"]] = traffic["end_to_end"]
    for m in bench["end_to_end"]:
        if m["name"] == "setup_s":
            continue
        for cell in m["workloads"]:
            assert m["name"] in e2e_traffic[cell], (m["name"], cell)
    for m in bench["per_layer"]:
        path = REPO / "perfbench" / "layer_metrics" / f"{m['name']}.json"
        spec = json.loads(path.read_text())
        assert spec["name"] == m["name"]
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        reader = REPO / "perfbench" / "readers" / f"{spec['reader']}.py"
        assert reader.is_file(), reader


def test_files_under_paths_are_named_from_a_names_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in bench["paths"]:
        for path in (REPO / top).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert ok.match(str(path.relative_to(REPO))), path
