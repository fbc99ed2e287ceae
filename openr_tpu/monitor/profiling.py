"""Kernel tracing surface (SURVEY §5.1: "JAX profiler + xprof traces
for the SPF kernel, plus the same counter surface").

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

bench.py honors OPENR_BENCH_TRACE=<dir> and wraps its timed iterations;
TpuSpfSolver annotates solve/assembly phases so the xprof timeline
separates device solve time from host RIB assembly.

With a :class:`Counters` registry passed, every annotated span ALSO
records its wall duration into the windowed ``profile.<span>_ms``
histogram stat — so solver phase timings land on the same Prometheus
surface (and `breeze monitor fleet` distributions) as every other
latency in the system, whether or not an xprof session is active
(docs/Monitor.md).
"""

from __future__ import annotations

import contextlib
import logging
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


def annotate(name: str, counters=None):
    """Named trace span (xprof timeline row); no-op without jax. With
    `counters`, the span's wall duration is additionally recorded into
    the ``profile.<name>_ms`` Counters histogram — device-side phase
    closure onto the common metric surface."""
    inner = _raw_annotation(name)
    if counters is None:
        return inner
    return _TimedSpan(name, counters, inner)


def _raw_annotation(name: str):
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001
        return contextlib.nullcontext()


class _TimedSpan:
    """Context manager wrapping the (possibly no-op) jax annotation with
    a wall-clock timer recorded into Counters on exit. Nested spans each
    record their own duration (the outer includes the inner, as xprof
    timelines do). Re-entrant only via fresh instances — annotate()
    returns a new one per call."""

    __slots__ = ("name", "counters", "inner", "_t0")

    def __init__(self, name: str, counters, inner):
        self.name = name
        self.counters = counters
        self.inner = inner
        self._t0 = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        try:
            self.inner.__enter__()
        except Exception:  # noqa: BLE001 — profiling must never break prod
            self.inner = contextlib.nullcontext()
            self.inner.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        try:
            self.inner.__exit__(exc_type, exc, tb)
        except Exception:  # noqa: BLE001
            log.warning("trace annotation exit failed", exc_info=True)
        self.counters.add_value(
            f"profile.{self.name}_ms",
            (time.perf_counter() - self._t0) * 1e3,
        )
        # annotate-boundary HBM sample (docs/Monitor.md "Device
        # telemetry"): on backends with memory_stats this stamps the
        # device.<i>.hbm_* gauges right after the device work the span
        # wrapped; on CPU the first probe latches availability off and
        # this is a single flag test per span
        try:
            from openr_tpu.monitor import device as _device

            _device.sample_hbm(self.counters)
        except Exception:  # noqa: BLE001 — profiling must never break prod
            pass
        return False
