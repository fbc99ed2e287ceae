"""The per-layer metrics that read the program's span record and the
kernel's own counters (PR 25): a traced rehearsal prints them, the two
new readers leave their metric out where there is nothing to read, and
the idle share is right on a hand-made trace and on the recorded cut."""

import json
from pathlib import Path

import pytest
from perfbench_util import TINY_CELLS, last_line, load_benchmark, run_py, tiny_checkout

from perfbench.readers import idle_named_share, series_residual

DATA = Path(__file__).parent / "data"

#: what the accepted benchmark had before this PR
OLD = {
    "debounce_wait_ms", "decision_apply_ms", "compute_rib_ms",
    "fetch_bytes_per_event", "fib_program_ms", "spf_warm_kernel_ms",
    "window_compiles.flap", "assemble_ms", "spf_cold_kernel_ms",
    "spf_cold_roofline", "window_compiles.er100k",
}


def new_metrics(real_cell: str) -> set[str]:
    return {
        m["name"] for m in load_benchmark()["per_layer"]
        if m["name"] not in OLD and real_cell in m["workloads"]
    }


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("perfbench_spans"))


@pytest.mark.parametrize(
    "cell,real_cell,floor",
    [("tiny_fabric.metric_flap", "fabric10k.metric_flap", 16),
     ("tiny_er.full_rib", "er100k.full_rib", 6)],
)
def test_a_traced_rehearsal_prints_every_new_metric(checkout, cell, real_cell, floor):
    assert cell in TINY_CELLS
    line = last_line(run_py(checkout, "--workload", cell, "--seed", "2147483777",
                            "--seconds", "4", "--trace", "1"))
    assert line["correct"] is True and line["failed"] == 0
    want = new_metrics(real_cell)
    assert len(want) >= floor
    # the CPU has no device plane: nothing to take an idle share of
    idle = {n for n in want if n.startswith("idle_named_share")}
    assert len(idle) == 1 and not idle & set(line["metrics"])
    got = line["metrics"]
    assert want - idle <= set(got), sorted(want - idle - set(got))
    units = {m["name"]: m["unit"] for m in load_benchmark()["per_layer"]}
    for name in want - idle:
        assert got[name]["unit"] == units[name]
    if "flap" in cell:
        # the spans nest: what they leave of their parents is small next
        # to the parents (a CPU host clock: a sanity bound, not a number)
        assert got["compute_rib_unattributed_ms"]["value"] < got["compute_rib_ms"]["value"] / 2
        assert -1.0 < got["flap_unattributed_ms"]["value"] < 5.0
        assert got["debounce_hold_ms"]["value"] <= got["debounce_wait_ms"]["value"]
        assert got["spf_warm_rounds"]["value"] >= 1
        assert got["scatter_calls_per_event"]["value"] >= 1
        assert got["warm_cone_cells"]["value"] >= 0
    else:
        assert got["spf_cold_sweeps"]["value"] >= 1
        assert got["spf_cold_tail_rounds"]["value"] >= 0
        assert got["cold_solve_wall_ms"]["value"] > 0
        assert -0.5 < got["full_rib_unattributed_ms"]["value"] < 2.0


def test_series_residual():
    obs = {"events": 3, "window_s": 1.0, "series": {
        "whole": [10.0, 20.0, 30.0], "a": [1.0, 2.0, 3.0], "b": [4.0, 4.0, 4.0],
        "empty": [],
    }}
    args = {"of": "whole", "less": ["a", "b"], "stat": "p50"}
    assert series_residual.read(obs, args) == 14.0  # 5, 14, 23
    assert series_residual.read(obs, {**args, "stat": "mean"}) == 14.0
    # a program without the span records no such series: nothing to read
    for missing in ({**args, "of": "nowhere"}, {**args, "less": ["a", "nowhere"]},
                    {**args, "less": ["a", "empty"]}, {**args, "less": []}):
        assert series_residual.read(obs, missing) is None
    assert series_residual.read({"series": {}, "events": 0}, args) is None


def hand_made_trace():
    ms = 1_000_000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["while.1", 10 * ms, 10 * ms], ["fusion.2", 12 * ms, 2 * ms],
                ["while.1", 60 * ms, 20 * ms],
                ["scatter.3", 95 * ms, 10 * ms],  # runs past the window's end
            ]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ["perfbench:window", 0, 100 * ms],
                # envelopes name nothing: the coroutine, the loop's wait,
                # the solver call around its phases, the timer asleep
                ["decision:debounce_wait", 0, 5 * ms],
                ["decision:rebuild", 5 * ms, 50 * ms],     # [5, 55)
                ["decision:compute_diff", 6 * ms, 44 * ms],
                ["decision:apply_snapshot", 5 * ms, 1 * ms],  # [5, 6)
                ["fib:program", 85 * ms, 20 * ms],         # [85, 105)
                ["not_the_programs:span", 40 * ms, 20 * ms],
            ]},
            {"name": "worker", "events": [
                ["decision:compute_rib", 6 * ms, 42 * ms],
                ["spf:warm_solve", 8 * ms, 14 * ms],       # [8, 22)
                ["spf:warm_reassemble", 38 * ms, 7 * ms],  # [38, 45)
            ]},
        ]},
    ]}


def test_idle_named_share_on_a_hand_made_trace():
    # busy [10,20) [60,80) [95,100): idle [0,10) [20,60) [80,95) = 65 ms.
    # Working spans: [5,6) [8,22) [38,45) [85,105); of the idle, [5,6) +
    # [8,10) + [20,22) + [38,45) + [85,95) = 22 ms. An overlap is counted
    # once however many spans cover it, a gap is split (no span has to
    # cover half of it to own its part), and the four envelopes, which
    # cover [0,55) between them, name nothing.
    assert idle_named_share.named_idle_share(hand_made_trace()) == pytest.approx(
        100 * 22 / 65)
    only_envelopes = hand_made_trace()
    only_envelopes["planes"][1]["lines"][1]["events"] = []
    only_envelopes["planes"][1]["lines"][0]["events"] = [
        e for e in only_envelopes["planes"][1]["lines"][0]["events"]
        if e[0] == "perfbench:window" or e[0] in idle_named_share.ENVELOPES
    ]
    assert idle_named_share.named_idle_share(only_envelopes) == 0.0


def test_idle_named_share_reads_nothing_without_a_window_or_a_device():
    trace = hand_made_trace()
    no_device = {"planes": trace["planes"][1:]}
    assert idle_named_share.named_idle_share(no_device) is None
    no_window = json.loads(json.dumps(trace))
    no_window["planes"][1]["lines"][0]["events"].pop(0)
    assert idle_named_share.named_idle_share(no_window) is None
    # an untraced run, and a traced one whose trace has no device plane
    assert idle_named_share.read({"series": {}}, {}) is None
    assert idle_named_share.read({"trace": {"devices": 0}}, {}) is None


def test_idle_named_share_on_the_recorded_trace():
    """The cut of PR 24's trace: two flaps whose only program span is
    `spf:warm_solve`, so most of the idle time is nobody's."""
    with open(DATA / "flap_trace_cut.json") as f:
        cut = json.load(f)
    share = idle_named_share.named_idle_share(cut["trace"])
    want = cut["expected"]
    idle_s = want["window_s"] - want["busy_s"]
    spans = [
        (s, s + d) for p in cut["trace"]["planes"] for ln in p["lines"]
        for n, s, d in ln["events"] if n.startswith("spf:")
    ]
    assert spans and 0.0 < share < 20.0
    # no more idle time can be named than the spans are long
    assert share * idle_s / 100 <= sum(e - s for s, e in spans) / 1e9 + 1e-9
