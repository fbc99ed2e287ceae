"""Kernel tracing surface (SURVEY §5.1: "JAX profiler + xprof traces
for the SPF kernel, plus the same counter surface") and the one span
source of the event path (docs/Monitor.md "Spans").

Wraps jax.profiler so the rest of the framework never imports jax for
observability alone, and so tracing degrades to a no-op on hosts where
the profiler cannot start (the CPU control plane keeps running).

Usage:
  with profiling.trace("/tmp/spf_trace"):      # xprof trace directory
      solver.compute_routes(...)
  with profiling.annotate("spf:solve"):        # named span inside it
      ...
  with profiling.annotate("spf:solve", counters=node_counters):
      ...  # ALSO records wall ms into the `profile.spf:solve_ms` stat
  with profiling.collect() as rec:             # one request's record
      with profiling.annotate("spf:solve"):
          ...
  rec.ms["spf:solve"], rec.spans

One span, three sinks:

  * a ``jax.profiler.TraceAnnotation`` — a row on the xprof timeline,
    i.e. on the device trace's clock, so an idle gap of the device can
    be given to what the host was doing in it;
  * with a :class:`Counters` registry passed, the wall duration lands in
    the windowed ``profile.<span>_ms`` histogram stat — the Prometheus
    surface every other latency of the system is on;
  * with a collector open (:func:`collect`), ``(name, parent, start,
    end)`` is appended to the request's :class:`SpanRecord`. The record
    rides a ``contextvars.ContextVar``, so it follows
    ``asyncio.to_thread`` into the solver thread. ``Decision.
    last_breakdown_ms`` and ``TpuSpfSolver.last_phase_ms`` are views of
    such records.

With no collector open and no ``counters`` a span writes to the trace
alone. Leaving a span never calls the device (the HBM gauges are sampled
at rebuild edges, decision.py).

The cyclic garbage collector's pauses are spans of the same record
("The collector's pauses" below): the first ``collect()``,
``annotate()`` or ``start()`` of a process hooks ``gc.callbacks`` once.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import logging
import threading
import time

log = logging.getLogger(__name__)


@contextlib.contextmanager
def trace(trace_dir: str | None):
    """jax.profiler.trace(trace_dir), or a no-op when dir is falsy or
    the profiler is unavailable/fails to start (unwritable directory,
    session already active, ...)."""
    if not trace_dir:
        yield
        return
    cm = None
    try:
        import jax

        cm = jax.profiler.trace(trace_dir)
        cm.__enter__()  # start_trace runs HERE — keep it under the guard
    except Exception:  # noqa: BLE001 — profiling must never break prod
        log.warning("jax profiler unavailable; tracing disabled")
        yield
        return
    try:
        yield
    finally:
        try:
            cm.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — export failure (bad dir, ...)
            log.warning("jax profiler trace export failed", exc_info=True)


# ------------------------------------------------------------ the record

#: the open collector's record and the innermost open span's name, per
#: context: a task or a `to_thread` worker sees its caller's
_RECORD: contextvars.ContextVar["SpanRecord | None"] = contextvars.ContextVar(
    "openr_span_record", default=None
)
_PARENT: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "openr_span_parent", default=None
)


class SpanRecord:
    """The spans of one request (one route rebuild, one solver call):
    ``spans`` holds ``(name, parent, start, end)`` in closing order,
    times in ``time.perf_counter()`` seconds, ``parent`` the name of the
    span that was innermost when this one opened (None at the top). A
    record belongs to one request whose phases run one after another,
    possibly on two threads; ``list.append`` is all that touches it
    while spans close, the sums are taken when read."""

    __slots__ = ("spans", "t0")

    def __init__(self):
        self.spans: list[tuple[str, str | None, float, float]] = []
        self.t0 = time.perf_counter()

    @property
    def ms(self) -> dict[str, float]:
        """Wall milliseconds by span name (same-named spans add up)."""
        out: dict[str, float] = {}
        for name, _parent, start, end in self.spans:
            out[name] = out.get(name, 0.0) + (end - start) * 1e3
        return out

    def elapsed_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3


class collect:
    """Open a record for the spans closed under this block. Nested under
    another collector, the inner record is the caller's alone while
    open, and its spans are handed to the outer one on the way out (top
    level spans become children of the outer block's open span)."""

    __slots__ = ("record", "_tokens")

    def __init__(self):
        self.record = SpanRecord()
        self._tokens = None

    def __enter__(self) -> SpanRecord:
        if not _gc_hooked:
            install_gc_hook()
        self._tokens = (_RECORD.set(self.record), _PARENT.set(None))
        return self.record

    def __exit__(self, exc_type, exc, tb):
        rec_token, parent_token = self._tokens
        _PARENT.reset(parent_token)
        _RECORD.reset(rec_token)
        outer = _RECORD.get()
        if outer is not None:
            parent = _PARENT.get()
            outer.spans.extend(
                (n, parent if p is None else p, s, e)
                for n, p, s, e in self.record.spans
            )
        return False


# -------------------------------------------------------------- the span

#: jax.profiler.TraceAnnotation, resolved on first use: None = not yet
#: looked up, False = no jax here (spans are clocks only)
_ANNOTATION_CLS = None


def _annotation(name: str):
    global _ANNOTATION_CLS
    if _ANNOTATION_CLS is None:
        try:
            import jax

            _ANNOTATION_CLS = jax.profiler.TraceAnnotation
        except Exception:  # noqa: BLE001 — no jax: the control plane runs on
            _ANNOTATION_CLS = False
    if _ANNOTATION_CLS is False:
        return None
    try:
        return _ANNOTATION_CLS(name)
    except Exception:  # noqa: BLE001 — profiling must never break prod
        return None


def annotate(name: str, counters=None) -> "Span":
    """Named trace span (xprof timeline row; a clock pair alone without
    jax). With `counters`, the span's wall duration is additionally
    recorded into the ``profile.<name>_ms`` Counters histogram; under an
    open collector it is appended to the request's record. `.ms` holds
    the duration once the block is left."""
    return Span(name, counters)


def start(name: str) -> "Span":
    """A span entered now and left by `Span.stop(record)`, for a phase
    that begins in one task and ends in another of the same thread
    (Decision's debounce hold). It is nobody's parent."""
    return Span(name)._open()


def stamp(name: str, start: float | None) -> None:
    """A span of the record alone, from `start` (a `perf_counter()`
    reading taken earlier, possibly in another thread) to now: for a
    hand-off that begins in one thread and ends in another, where a
    `TraceAnnotation` (entered and left by one thread) cannot go. Child
    of the span open here; nothing without an open record or a start."""
    record = _RECORD.get()
    if record is not None and start is not None:
        record.spans.append((name, _PARENT.get(), start, time.perf_counter()))


class Span:
    """The timed span: the (possibly absent) jax annotation, a
    `perf_counter` pair, and on the way out the Counters stat and the
    open record. Nested spans each record their own duration (the outer
    includes the inner, as xprof timelines do). `ms` holds the duration
    once the span is closed. One use per instance."""

    __slots__ = ("name", "counters", "ms", "_inner", "_t0", "_token")

    def __init__(self, name: str, counters=None):
        self.name = name
        self.counters = counters
        self.ms = 0.0
        self._inner = None
        self._t0 = 0.0
        self._token = None

    def _open(self) -> "Span":
        if not _gc_hooked:
            install_gc_hook()
        self._inner = _annotation(self.name)
        if self._inner is not None:
            try:
                self._inner.__enter__()
            except Exception:  # noqa: BLE001 — must never break prod
                self._inner = None
        self._t0 = time.perf_counter()
        return self

    def _close(self, record: SpanRecord | None, parent: str | None) -> None:
        t1 = time.perf_counter()
        if self._inner is not None:
            try:
                self._inner.__exit__(None, None, None)
            except Exception:  # noqa: BLE001
                log.warning("trace annotation exit failed", exc_info=True)
        self.ms = (t1 - self._t0) * 1e3
        if record is not None:
            record.spans.append((self.name, parent, self._t0, t1))
        if self.counters is not None:
            self.counters.add_value(f"profile.{self.name}_ms", self.ms)

    def __enter__(self) -> "Span":
        self._token = _PARENT.set(self.name)
        return self._open()

    def __exit__(self, exc_type, exc, tb):
        _PARENT.reset(self._token)
        self._close(_RECORD.get(), _PARENT.get())
        return False

    def stop(self, record: SpanRecord | None = None) -> None:
        """Leave a span opened by `start`, into `record` at its top."""
        self._close(record, None)


# ------------------------------------------------ the collector's pauses


class GcTotals:
    """The cyclic garbage collector over the life of the process, by
    generation (index 0, 1, 2; 2 is a full collection): collections,
    seconds the interpreter stood still in them (start callback to stop
    callback, the collection's span inside), objects they freed.
    Written by `_on_gc` alone; a collection runs with the interpreter's
    `collecting` flag up, its callbacks included, so there is one writer
    at a time."""

    __slots__ = ("collections", "pause_s", "collected")

    def __init__(self):
        self.collections = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self.collected = [0, 0, 0]


_GC = GcTotals()
_GC_LOCK = threading.Lock()
_gc_hooked = False
#: the collection in flight: its start and, where it became one, its span
_gc_t0: float | None = None
_gc_span: Span | None = None


def _on_gc(phase: str, info: dict) -> None:
    """The `gc.callbacks` hook. Any generation: two clock reads and the
    totals (a fabric event sets off on the order of a hundred young
    collections). A collection of generation 1 or 2 that starts while a
    span is open in this thread's context is a span too, child of the
    one it interrupted and named by that span's module prefix
    (`spf:gc`, `decision:gc`, `fib:gc`: perfbench/trace_reduce.py keeps
    the row like any span of the program): opened here at "start" and
    closed at "stop" by the same thread, into the trace, the open
    record and the totals.
    With no span open (the loop asleep, a caller outside the program)
    it lands in the totals alone."""
    global _gc_t0, _gc_span
    if phase == "start":
        _gc_t0 = time.perf_counter()
        if info["generation"]:
            parent = _PARENT.get()
            if parent is not None:
                _gc_span = Span(parent.partition(":")[0] + ":gc")._open()
        return
    t0, span = _gc_t0, _gc_span
    if t0 is None:
        return  # hooked between this collection's start and its stop
    _gc_t0 = _gc_span = None
    if span is not None:
        span._close(_RECORD.get(), _PARENT.get())
    gen = info["generation"]
    _GC.collections[gen] += 1
    _GC.pause_s[gen] += time.perf_counter() - t0
    _GC.collected[gen] += info["collected"]


def install_gc_hook() -> None:
    """Hook the collector, once a process however often it is asked."""
    global _gc_hooked
    with _GC_LOCK:
        if not _gc_hooked:
            gc.callbacks.append(_on_gc)
            _gc_hooked = True


def remove_gc_hook() -> None:
    """Take the hook off again (tests); the totals stay. The next
    `collect()`, `annotate()` or `start()` puts it back."""
    global _gc_hooked, _gc_t0, _gc_span
    with _GC_LOCK:
        if _gc_hooked:
            gc.callbacks.remove(_on_gc)
            _gc_hooked = False
            _gc_t0 = _gc_span = None


def gc_totals() -> dict[str, float]:
    """The process totals as the `runtime.gc.*` gauges carry them
    (docs/Monitor.md "Runtime: the garbage collector"): cumulative, so
    a reader takes the difference of two readings."""
    return {
        "collections": sum(_GC.collections),
        "pause_ms": sum(_GC.pause_s) * 1e3,
        "full_collections": _GC.collections[2],
        "full_pause_ms": _GC.pause_s[2] * 1e3,
        "collected": sum(_GC.collected),
    }


def export_gc_to(counters) -> None:
    """Stamp the totals into a Counters registry, as `compile_ledger`
    and `work_ledger` hand theirs over at a rebuild's edge. Values are
    the process's: one collector serves every in-process node."""
    totals = gc_totals()
    counters.set("runtime.gc.collections", totals["collections"])
    counters.set("runtime.gc.pause_ms", totals["pause_ms"])
    counters.set("runtime.gc.full_collections", totals["full_collections"])
    counters.set("runtime.gc.full_pause_ms", totals["full_pause_ms"])
