"""monitor/profiling.py coverage: no-op degradation without jax, nested
annotate spans, the duration→Counters recording that puts solver phase
timings on the Prometheus surface, and the per-request span record
(collect) that Decision.last_breakdown_ms and TpuSpfSolver.last_phase_ms
are views of."""

import ast
import asyncio
import pathlib
import re
import sys
import time

import pytest

from openr_tpu.monitor import names, profiling
from openr_tpu.monitor.counters import Counters


@pytest.fixture
def no_jax(monkeypatch):
    """`import jax` raises inside profiling's guarded lookup, which has
    not latched an answer yet (and forgets this one afterwards)."""
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setattr(profiling, "_ANNOTATION_CLS", None)


class _NoJax:
    """monkeypatch sys.modules['jax'] to None → `import jax` raises
    ImportError inside profiling's guarded imports."""


def test_annotate_noop_without_jax(no_jax):
    with profiling.annotate("spf:solve"):
        pass  # must not raise
    assert profiling._ANNOTATION_CLS is False  # looked up once, latched


def test_trace_noop_without_jax(monkeypatch, caplog):
    monkeypatch.setitem(sys.modules, "jax", None)
    with profiling.trace("/tmp/definitely-not-used"):
        pass
    assert any(
        "profiler unavailable" in r.message for r in caplog.records
    )


def test_trace_falsy_dir_is_noop():
    # no jax import at all on the falsy-dir path
    with profiling.trace(None):
        pass
    with profiling.trace(""):
        pass


def test_annotate_records_duration_into_counters():
    c = Counters()
    with profiling.annotate("spf:solve", counters=c):
        time.sleep(0.01)
    s = c.stats.get("profile.spf:solve_ms")
    assert s is not None and s.count == 1
    assert s.last >= 5.0  # slept 10 ms; generous lower bound
    # exported through the standard snapshot surface
    snap = c.snapshot()
    assert snap["profile.spf:solve_ms.count"] == 1


def test_annotate_records_even_without_jax(no_jax):
    c = Counters()
    with profiling.annotate("spf:rib_assembly", counters=c):
        pass
    assert c.stats["profile.spf:rib_assembly_ms"].count == 1


def test_nested_annotate_outer_includes_inner():
    c = Counters()
    with profiling.annotate("outer", counters=c):
        with profiling.annotate("inner", counters=c):
            time.sleep(0.005)
    outer = c.stats["profile.outer_ms"]
    inner = c.stats["profile.inner_ms"]
    assert outer.count == 1 and inner.count == 1
    # xprof-timeline semantics: the outer span contains the inner one
    assert outer.last >= inner.last


def test_annotate_duration_recorded_on_exception():
    c = Counters()
    try:
        with profiling.annotate("boom", counters=c):
            raise RuntimeError("solver failed")
    except RuntimeError:
        pass
    assert c.stats["profile.boom_ms"].count == 1


def test_annotate_reentrant_fresh_instances():
    c = Counters()
    for _ in range(3):
        with profiling.annotate("loop", counters=c):
            pass
    assert c.stats["profile.loop_ms"].count == 3


# ------------------------------------------------------ the span record


def test_collector_gathers_nested_spans_with_their_parent():
    with profiling.collect() as rec:
        with profiling.annotate("decision:outer"):
            with profiling.annotate("spf:inner"):
                time.sleep(0.004)
            with profiling.annotate("spf:inner"):
                time.sleep(0.002)
            time.sleep(0.003)
    # closing order, each with the span that was open around it
    assert [(n, p) for n, p, _s, _e in rec.spans] == [
        ("spf:inner", "decision:outer"),
        ("spf:inner", "decision:outer"),
        ("decision:outer", None),
    ]
    assert all(e >= s >= rec.t0 for _n, _p, s, e in rec.spans)
    ms = rec.ms
    assert ms["spf:inner"] >= 5.0  # same-named spans add up
    assert ms["decision:outer"] >= ms["spf:inner"] + 2.0
    # closed with the block: a later span lands in no record
    with profiling.annotate("spf:x"):
        pass
    assert len(rec.spans) == 3


def test_collector_follows_asyncio_to_thread():
    import threading

    seen = {}

    def worker():
        seen["thread"] = threading.get_ident()
        with profiling.annotate("spf:in_thread"):
            pass

    async def body():
        with profiling.collect() as rec:
            with profiling.annotate("decision:compute_diff"):
                await asyncio.to_thread(worker)
        return rec

    rec = asyncio.run(body())
    assert seen["thread"] != threading.get_ident()
    assert [(n, p) for n, p, _s, _e in rec.spans] == [
        ("spf:in_thread", "decision:compute_diff"),
        ("decision:compute_diff", None),
    ]


def test_nested_collector_hands_its_spans_to_the_outer_one():
    with profiling.collect() as outer:
        with profiling.annotate("decision:compute_rib"):
            with profiling.collect() as inner:
                with profiling.annotate("spf:prepare"):
                    with profiling.annotate("spf:to_csr"):
                        pass
            assert [n for n, *_ in inner.spans] == [
                "spf:to_csr", "spf:prepare",
            ]
            assert inner.spans[1][1] is None  # top of its own record
    assert [(n, p) for n, p, _s, _e in outer.spans] == [
        ("spf:to_csr", "spf:prepare"),
        ("spf:prepare", "decision:compute_rib"),
        ("decision:compute_rib", None),
    ]


def test_span_exit_makes_no_device_call(monkeypatch):
    """Leaving a span reads a clock and appends a tuple: no
    memory_stats, no sample_hbm (the HBM gauges are sampled at rebuild
    edges)."""
    import jax

    from openr_tpu.monitor import device

    calls = []
    monkeypatch.setattr(
        device, "sample_hbm", lambda *a, **k: calls.append("sample_hbm")
    )
    monkeypatch.setattr(
        device._TELEMETRY, "sample_hbm",
        lambda *a, **k: calls.append("telemetry.sample_hbm"),
    )
    dev_cls = type(jax.devices()[0])
    monkeypatch.setattr(
        dev_cls, "memory_stats",
        lambda self: calls.append("memory_stats"), raising=False,
    )
    c = Counters()
    with profiling.collect():
        with profiling.annotate("spf:x", counters=c):
            pass
    with profiling.annotate("spf:x", counters=c):
        pass
    assert calls == []
    assert c.stats["profile.spf:x_ms"].count == 2
    assert not any(k.startswith("device.") for k in c.counters)


def test_no_collector_and_no_counters_writes_to_the_trace_alone(
    monkeypatch,
):
    rows = []

    class Row:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            rows.append(("enter", self.name))

        def __exit__(self, *exc):
            rows.append(("exit", self.name))

    monkeypatch.setattr(profiling, "_ANNOTATION_CLS", Row)
    with profiling.collect() as rec:
        pass
    with profiling.annotate("spf:bare") as span:
        assert rows == [("enter", "spf:bare")]
    assert rows == [("enter", "spf:bare"), ("exit", "spf:bare")]
    assert span.counters is None and rec.spans == []


def test_annotate_hands_back_the_closed_block_s_ms():
    """One clock pair feeds the trace row, the record and whatever the
    caller does with `.ms` (Fib's fib.program_ms)."""
    c = Counters()
    with profiling.collect() as rec:
        with profiling.annotate("fib:program", counters=c) as span:
            assert span.ms == 0.0
            time.sleep(0.002)
    assert span.ms >= 1.5
    assert rec.ms["fib:program"] == span.ms
    assert c.stats["profile.fib:program_ms"].count == 1


def test_started_span_lands_in_the_record_it_is_stopped_into():
    held = profiling.start("decision:debounce_wait")
    time.sleep(0.002)
    with profiling.collect() as rec:
        held.stop(rec)
        with profiling.annotate("decision:rebuild"):
            pass
    assert [(n, p) for n, p, _s, _e in rec.spans] == [
        ("decision:debounce_wait", None), ("decision:rebuild", None),
    ]
    assert rec.ms["decision:debounce_wait"] >= 1.5
    assert rec.spans[0][2] < rec.t0  # it began before the record did
    profiling.start("decision:debounce_wait").stop()  # dropped: no record


def test_a_span_closes_into_the_record_when_its_block_raises():
    with pytest.raises(RuntimeError):
        with profiling.collect() as rec:
            with profiling.annotate("spf:rib_assembly"):
                with profiling.annotate("spf:rib_election"):
                    raise RuntimeError("assembly failed")
    assert [(n, p) for n, p, _s, _e in rec.spans] == [
        ("spf:rib_election", "spf:rib_assembly"),
        ("spf:rib_assembly", None),
    ]
    # and the parent is restored: the next span is at the top again
    with profiling.collect() as rec2:
        with profiling.annotate("spf:to_csr"):
            pass
    assert rec2.spans[0][1] is None


# ------------------------------------------------------ the vocabulary

_SPAN_CALLS = {"annotate", "start"}


def _span_literals():
    root = pathlib.Path(profiling.__file__).resolve().parents[1]
    for path in sorted(root.rglob("*.py")):
        if path.name == "profiling.py":
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            if not (isinstance(fn, ast.Attribute) and fn.attr in _SPAN_CALLS):
                continue
            owner = fn.value
            if not (isinstance(owner, ast.Name) and owner.id == "profiling"):
                continue
            arg = node.args[0]
            assert isinstance(arg, ast.Constant) and isinstance(
                arg.value, str
            ), f"{path}:{node.lineno}: a span name is a literal"
            yield path, node.lineno, arg.value


def test_every_span_name_is_registered_prefixed_and_documented():
    """monitor/names.py SPANS is the span vocabulary: every call site
    uses a literal from it, every name has the prefix
    perfbench/trace_reduce.py keeps from the host planes, every
    registered name is opened somewhere and listed in docs/Monitor.md."""
    used = {}
    for path, line, name in _span_literals():
        used.setdefault(name, f"{path}:{line}")
    unknown = {n: at for n, at in used.items() if n not in names.SPANS}
    assert not unknown, unknown
    assert set(used) == set(names.SPANS)
    prefix = re.compile(r"^(spf|decision|fib|kvstore):[a-z_]+$")
    assert all(prefix.match(n) for n in names.SPANS)
    assert set(names.REBUILD_SPANS) < names.SPANS
    assert len(set(names.REBUILD_SPANS)) == len(names.REBUILD_SPANS)
    doc = (
        pathlib.Path(profiling.__file__).resolve().parents[2]
        / "docs" / "Monitor.md"
    ).read_text()
    missing = [n for n in sorted(names.SPANS) if f"`{n}`" not in doc]
    assert not missing, missing
