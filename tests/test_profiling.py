"""monitor/profiling.py coverage: no-op degradation without jax, nested
annotate spans, the duration→Counters recording that puts solver phase
timings on the Prometheus surface, and the per-request span record
(collect) that Decision.last_breakdown_ms and TpuSpfSolver.last_phase_ms
are views of."""

import ast
import asyncio
import gc
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


@pytest.fixture(autouse=True)
def only_forced_collections():
    """The collector runs where a test calls it and nowhere else, so
    that a record holds the spans the test opened and no `<prefix>:gc`
    of a collection that happened to fall into it."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


@pytest.fixture
def trace_rows(monkeypatch):
    """In place of jax's TraceAnnotation: the rows opened and closed,
    `("enter" | "exit", name)` in order."""
    rows = []

    class Row:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            rows.append(("enter", self.name))

        def __exit__(self, *exc):
            rows.append(("exit", self.name))

    monkeypatch.setattr(profiling, "_ANNOTATION_CLS", Row)
    return rows


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
    trace_rows,
):
    rows = trace_rows
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


# ------------------------------------------------ the collector's pauses


def _grown(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def test_a_collection_inside_an_open_span_is_its_gc_child(trace_rows):
    """Generation 1 or 2, started under an open span: a `<prefix>:gc`
    span with the three sinks every span has (trace row, record,
    totals), child of the span it interrupted."""
    rows = trace_rows
    before = profiling.gc_totals()
    with profiling.collect() as rec:
        with profiling.annotate("spf:warm_reassemble"):
            gc.collect()
        with profiling.annotate("decision:rebuild"):
            with profiling.annotate("decision:publish"):
                gc.collect(1)
        with profiling.annotate("fib:program"):
            gc.collect(2)
    assert [(n, p) for n, p, _s, _e in rec.spans] == [
        ("spf:gc", "spf:warm_reassemble"), ("spf:warm_reassemble", None),
        ("decision:gc", "decision:publish"),
        ("decision:publish", "decision:rebuild"), ("decision:rebuild", None),
        ("fib:gc", "fib:program"), ("fib:program", None),
    ]
    at = {(n, p): (s, e) for n, p, s, e in rec.spans}
    for child, parent in (
        (("spf:gc", "spf:warm_reassemble"), ("spf:warm_reassemble", None)),
        (("decision:gc", "decision:publish"),
         ("decision:publish", "decision:rebuild")),
        (("fib:gc", "fib:program"), ("fib:program", None)),
    ):
        assert at[parent][0] <= at[child][0] <= at[child][1] <= at[parent][1]
    # the row opens inside its parent's and closes before it
    assert rows[:4] == [
        ("enter", "spf:warm_reassemble"), ("enter", "spf:gc"),
        ("exit", "spf:gc"), ("exit", "spf:warm_reassemble"),
    ]
    assert [name for kind, name in rows if kind == "enter"].count(
        "decision:gc") == 1
    grew = _grown(before, profiling.gc_totals())
    assert grew["collections"] == 3 and grew["full_collections"] == 2
    assert grew["pause_ms"] >= grew["full_pause_ms"] > 0.0
    # the totals hold the spans' time and the hook's own two reads
    assert grew["pause_ms"] >= sum(
        ms for name, ms in rec.ms.items() if name.endswith(":gc"))
    assert set(rec.ms) >= {"spf:gc", "decision:gc", "fib:gc"}


def test_a_collection_under_no_span_lands_in_the_totals_alone(trace_rows):
    before = profiling.gc_totals()
    with profiling.collect() as rec:  # a record, but no span open
        gc.collect()
    gc.collect()  # no record either
    held = profiling.start("decision:debounce_wait")  # nobody's parent
    gc.collect()
    held.stop()
    assert rec.spans == [] and trace_rows == [
        ("enter", "decision:debounce_wait"), ("exit", "decision:debounce_wait"),
    ]
    grew = _grown(before, profiling.gc_totals())
    assert grew["collections"] == grew["full_collections"] == 3
    assert grew["full_pause_ms"] > 0.0


def test_a_young_collection_opens_nothing():
    before = profiling.gc_totals()
    with profiling.collect() as rec:
        with profiling.annotate("spf:rib_unicast"):
            gc.collect(0)
    assert [(n, p) for n, p, _s, _e in rec.spans] == [
        ("spf:rib_unicast", None)]
    grew = _grown(before, profiling.gc_totals())
    assert grew["collections"] == 1 and grew["full_collections"] == 0
    assert grew["pause_ms"] > 0.0 and grew["full_pause_ms"] == 0.0


def test_what_a_collection_freed_is_counted():
    class Node:
        pass

    before = profiling.gc_totals()
    with profiling.annotate("spf:x"):
        for _ in range(50):
            a, b = Node(), Node()
            a.other, b.other = b, a  # a cycle only the collector frees
        del a, b
        gc.collect()
    assert profiling.gc_totals()["collected"] - before["collected"] >= 100


def test_one_hook_a_process_and_it_comes_off_cleanly():
    for _ in range(3):
        with profiling.collect():
            with profiling.annotate("spf:x"):
                pass
        profiling.start("decision:debounce_wait").stop()
    assert gc.callbacks.count(profiling._on_gc) == 1
    profiling.remove_gc_hook()
    profiling.remove_gc_hook()  # and again: nothing left to take off
    assert profiling._on_gc not in gc.callbacks
    before = profiling.gc_totals()
    gc.collect()
    assert profiling.gc_totals() == before  # unhooked: it counts nothing
    # the next span of the process puts it back, once
    with profiling.annotate("spf:x"):
        gc.collect()
    with profiling.collect():
        pass
    assert gc.callbacks.count(profiling._on_gc) == 1
    assert profiling.gc_totals()["full_collections"] == (
        before["full_collections"] + 1)


def test_the_hook_counts_and_records_without_jax(no_jax):
    """JAX_PLATFORMS=cpu and no profiler, or no jax at all: the span is
    a clock pair, as every span is there."""
    before = profiling.gc_totals()
    with profiling.collect() as rec:
        with profiling.annotate("spf:solve"):
            gc.collect()
    assert [n for n, *_ in rec.spans] == ["spf:gc", "spf:solve"]
    assert profiling.gc_totals()["full_collections"] == (
        before["full_collections"] + 1)


def test_export_gc_to_sets_the_four_cumulative_gauges():
    c = Counters()
    profiling.export_gc_to(c)
    first = {k: v for k, v in c.snapshot().items() if k.startswith("runtime.")}
    assert set(first) == {
        "runtime.gc.collections", "runtime.gc.pause_ms",
        "runtime.gc.full_collections", "runtime.gc.full_pause_ms",
    }
    assert set(first) <= names.COUNTERS and set(first) <= names.DOCUMENTED
    with profiling.annotate("spf:x"):
        gc.collect()
        gc.collect(0)
    profiling.export_gc_to(c)
    second = c.snapshot()
    grew = {k.rpartition(".")[2]: second[k] - v for k, v in first.items()}
    assert grew["collections"] == 2 and grew["full_collections"] == 1
    assert grew["pause_ms"] > grew["full_pause_ms"] > 0.0


# -------------------------------------------- spans of the record alone


def test_a_stamp_spans_two_threads_in_the_record_alone(monkeypatch):
    """`stamp`: a hand-off that begins in one thread and ends in
    another is a span of the record and no row of the trace."""
    monkeypatch.setattr(
        profiling, "_ANNOTATION_CLS",
        lambda name: pytest.fail(f"a trace row was opened: {name}")
        if "thread" in name else None,
    )

    def worker(called_at):
        profiling.stamp("decision:thread_start", called_at)
        time.sleep(0.002)
        return time.perf_counter()

    async def body():
        with profiling.collect() as rec:
            with profiling.annotate("decision:compute_diff"):
                done_at = await asyncio.to_thread(worker, time.perf_counter())
                profiling.stamp("decision:thread_return", done_at)
        return rec

    rec = asyncio.run(body())
    assert [(n, p) for n, p, _s, _e in rec.spans] == [
        ("decision:thread_start", "decision:compute_diff"),
        ("decision:thread_return", "decision:compute_diff"),
        ("decision:compute_diff", None),
    ]
    (_n, _p, s0, e0), (_n, _p, s1, e1), (_n, _p, s, e) = rec.spans
    assert s <= s0 <= e0 <= s1 <= e1 <= e
    assert e0 + 0.0015 <= s1  # the worker's own time lies between the two
    # no record open, or no start handed over: nothing, and no error
    profiling.stamp("decision:thread_start", time.perf_counter())
    with profiling.collect() as rec2:
        profiling.stamp("decision:thread_return", None)
    assert rec2.spans == []


# ------------------------------- the spans of a topology base's tables


def test_a_structural_rebuild_opens_table_build_and_upload_once_under_dispatch():
    """`spf:table_build` and `spf:upload` (PR 38): a topology base the
    device has no tables of opens each once, as children of
    `spf:dispatch` whose sum it holds, and `upload_bytes` moves by the
    tables' bytes; a metric-only change on that base opens neither."""
    import dataclasses

    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.utils.topogen import fat_tree

    ls, ps = LinkState(), PrefixState()
    adj_dbs, prefix_dbs = fat_tree(4)
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    me = adj_dbs[-1].this_node_name
    solver = TpuSpfSolver(native_rib="off")
    solver.compute_routes(ls, ps, me)

    def rebuild():
        bytes0 = solver.dev_cache_stats["upload_bytes"]
        with profiling.collect() as rec:
            solver.compute_routes(ls, ps, me)
        return rec, solver.dev_cache_stats["upload_bytes"] - bytes0

    # an overload bit: structural, so the CSR and its tables are built anew
    drained = dataclasses.replace(adj_dbs[0], is_overloaded=True)
    assert ls.update_adjacency_db_delta(drained) == (True, None)
    rec, uploaded = rebuild()
    opened = [(n, p) for n, p, _s, _e in rec.spans]
    assert opened.count(("spf:table_build", "spf:dispatch")) == 1
    assert opened.count(("spf:upload", "spf:dispatch")) == 1
    assert [n for n, _p in opened].count("spf:dispatch") == 1
    ms = rec.ms
    assert 0 < ms["spf:table_build"] and 0 < ms["spf:upload"]
    assert ms["spf:table_build"] + ms["spf:upload"] <= ms["spf:dispatch"]
    split = solver._dev[ls.to_csr().base_version]["sets"]["split"]
    tables = [v for v in split.values() if hasattr(v, "nbytes")]
    assert len(tables) == 7 and uploaded == sum(int(v.nbytes) for v in tables) > 0
    assert bool(split["over"].any())
    # a metric alone: the base stays, its tables are patched where they lie
    adjs = drained.adjacencies
    raised = dataclasses.replace(drained, adjacencies=(
        dataclasses.replace(adjs[0], metric=7), *adjs[1:]))
    changed, pairs = ls.update_adjacency_db_delta(raised)
    assert changed and pairs
    rec, uploaded = rebuild()
    assert not {"spf:table_build", "spf:upload"} & {n for n, *_ in rec.spans}
    assert "spf:patch_scatter" in rec.ms and uploaded == 0


# ------------------------------------------------------ the vocabulary

#: `stamp` makes a span of the record alone; a collection's span is made
#: in profiling.py itself, from the prefix of the span it interrupted
_SPAN_CALLS = {"annotate", "start", "stamp"}


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
    # a collection takes the module prefix of the span it falls into:
    # every prefix that opens a span has its `<prefix>:gc` registered
    gc_spans = {n.partition(":")[0] + ":gc" for n in used}
    assert gc_spans == {"spf:gc", "decision:gc", "fib:gc"}
    assert not gc_spans & set(used)
    assert set(used) | gc_spans == set(names.SPANS)
    new = {
        "spf:gc", "decision:gc", "decision:merge_scope", "decision:merge_full",
        "spf:warm_scope", "spf:general_items", "spf:warm_table_copy",
        "spf:warm_labels", "decision:thread_start", "decision:thread_return",
        "spf:ksp_prepare", "spf:table_build", "spf:upload",
    }
    assert new <= set(names.REBUILD_SPANS)
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
