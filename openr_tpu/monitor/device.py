"""Device telemetry plane: kernel cost ledger + HBM gauges + shard rows.

PR 10's observability plane stops at the host boundary — ``profiling``
records wall-ms spans and the PR 7 compile ledger counts compiles and
transfers, but nothing can say what a kernel *should* cost or how much
HBM it holds. This module closes the device side with three surfaces,
all riding the existing Counters/Prometheus path:

  * **Kernel cost ledger** — at trace time every canonical jitted entry
    point (``ops/`` and ``parallel/sharded_spf.py``) captures XLA's own
    static analysis of the compiled executable:
    ``lowered.compile().cost_analysis()`` (flops, bytes accessed,
    transcendentals) and ``.memory_analysis()`` (argument / output /
    temp / generated-code bytes — the executable's HBM footprint).
    Both are available on the CPU backend, so the whole surface is
    CI-testable without a TPU. Rows are keyed by the same function
    names the compile ledger parses out of ``jax_log_compiles``, and a
    row is (re)captured only when that ledger shows a fresh compile of
    the function — steady state does one dict lookup + int compare and
    never lowers, compiles, or syncs (the OR009 discipline). The AOT
    ``.compile()`` of an already-called jit function is a cache hit on
    jax 0.4.x (pinned by tests/test_device_telemetry.py under the jit
    sanitizer), so capture adds zero XLA compiles.
  * **HBM gauges** — per-device ``memory_stats()`` samples exported as
    ``device.<i>.hbm_bytes_in_use`` / ``hbm_peak_bytes`` /
    ``hbm_limit_bytes``, taken at decision rebuild edges (a profiling
    span's exit makes no device call). CPU backends return ``None``
    from ``memory_stats()``: the first all-None sample latches
    availability off and every later call is a single flag test.
  * **Shard rows** — per-device layout of a sharded output array read
    from its ``Sharding`` metadata WITHOUT touching ``shard.data``
    (which dispatches a ``_multi_slice`` program — a compile + a
    device sync). Used by the sharded-SPF span instrumentation and the
    MULTICHIP dryrun's per-device timing rows.

The joins are pure functions: :func:`efficiency_rows` merges captured
cost rows with the measured ``profile.<span>_ms`` stats into achieved
GFLOP/s / GB/s (``breeze device kernels``, ``ctrl
get_device_telemetry``). Like the compile ledger, the cost ledger is
process-global — compiled executables are a process resource shared by
every in-process node.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field

from openr_tpu.monitor import compile_ledger

log = logging.getLogger(__name__)


@dataclass
class KernelCostRow:
    """One captured executable's static cost/memory analysis."""

    fn: str
    #: the profiling span whose measured wall-ms this kernel's work
    #: lands in (the efficiency join key); None = no span association
    span: str | None = None
    #: whether that span measures the work to COMPLETION (a host
    #: materialization inside the span) or only the async dispatch.
    #: Dispatch-only spans are excluded from the achieved-throughput
    #: join — dividing full-kernel flops by dispatch wall would report
    #: unphysical GFLOP/s (review finding)
    span_complete: bool = True
    flops: float = 0.0
    bytes_accessed: float = 0.0
    transcendentals: float = 0.0
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0
    code_bytes: int = 0
    #: how many times this fn was (re)captured — tracks recompiles
    captures: int = 0
    shapes: str = ""
    error: str | None = None

    @property
    def resident_hbm_bytes(self) -> int:
        """The executable's device-memory footprint while running:
        arguments + outputs + XLA temp buffers + generated code."""
        return (
            self.arg_bytes + self.out_bytes + self.temp_bytes
            + self.code_bytes
        )

    def to_jsonable(self) -> dict:
        return {
            "fn": self.fn,
            "span": self.span,
            "span_complete": self.span_complete,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "transcendentals": self.transcendentals,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
            "code_bytes": self.code_bytes,
            "resident_hbm_bytes": self.resident_hbm_bytes,
            "captures": self.captures,
            "shapes": self.shapes,
            "error": self.error,
        }

    #: the numeric fields exported as ``jax.kernel.<fn>.<field>``
    EXPORT_FIELDS = (
        "flops", "bytes_accessed", "transcendentals", "arg_bytes",
        "out_bytes", "temp_bytes", "code_bytes", "captures",
    )


def _first_computation(cost) -> dict:
    """``Compiled.cost_analysis()`` returns a list of per-computation
    dicts on jax 0.4.x (one entry for a single-module executable) and a
    bare dict on newer lines; normalize to the entry-computation dict."""
    if isinstance(cost, (list, tuple)):
        return dict(cost[0]) if cost else {}
    return dict(cost or {})


class DeviceTelemetry:
    """Process-wide kernel cost ledger + HBM availability latch.
    Thread-safe like the compile ledger: solver calls may come from
    worker threads in benches."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: dict[str, KernelCostRow] = {}
        #: compile-ledger count of fn at its last capture — the
        #: recapture trigger (a fresh compile means a fresh executable
        #: whose analysis may differ)
        self._seen_compiles: dict[str, int] = {}
        self.enabled = True
        #: tri-state HBM availability: None = unprobed, False = backend
        #: has no memory_stats (CPU), True = gauges live
        self._hbm_state: bool | None = None

    # ------------------------------------------------------------ capture

    def observe(
        self,
        name: str,
        lower,
        span: str | None = None,
        span_complete: bool = True,
    ) -> None:
        """Steady-state-cheap capture guard: (re)capture ``name`` only
        when no row exists yet or the compile ledger has counted a
        fresh compile of it since the last capture. ``lower`` is a
        zero-arg callable returning the jitted function's ``Lowered``
        (``lambda: fn.lower(*the_call_args, **statics)``) — it is only
        invoked when a capture actually happens. ``span_complete=False``
        declares the span times only the async dispatch (see
        :class:`KernelCostRow`)."""
        if not self.enabled:
            return
        compiles = compile_ledger.compiles_of(name)
        with self._lock:
            have = name in self._rows
            seen = self._seen_compiles.get(name)
        if have and (seen == compiles or compiles == 0):
            # compiles == 0: ledger not installed — fall back to
            # capture-once-per-fn (the row exists, keep it)
            return
        self.capture(name, lower, span=span, span_complete=span_complete)

    def capture(
        self,
        name: str,
        lower,
        span: str | None = None,
        span_complete: bool = True,
    ) -> KernelCostRow:
        """Unconditionally capture ``name``'s cost/memory analysis and
        record it (the MULTICHIP dryrun uses this directly to get one
        row per mesh). Never raises: analysis failures land as an
        error row so telemetry can't break a solve."""
        row = KernelCostRow(fn=name, span=span, span_complete=span_complete)
        try:
            lowered = lower()
            compiled = lowered.compile()
            cost = _first_computation(compiled.cost_analysis())
            row.flops = float(cost.get("flops", 0.0))
            row.bytes_accessed = float(cost.get("bytes accessed", 0.0))
            row.transcendentals = float(cost.get("transcendentals", 0.0))
            mem = compiled.memory_analysis()
            if mem is not None:
                row.arg_bytes = int(
                    getattr(mem, "argument_size_in_bytes", 0)
                )
                row.out_bytes = int(getattr(mem, "output_size_in_bytes", 0))
                row.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0))
                row.code_bytes = int(
                    getattr(mem, "generated_code_size_in_bytes", 0)
                )
            avals = getattr(lowered, "in_avals", None)
            if avals is not None:
                try:
                    import jax

                    row.shapes = ",".join(
                        str(getattr(a, "shape", "?"))
                        for a in jax.tree_util.tree_leaves(avals)
                    )
                except Exception:  # noqa: BLE001 — cosmetic only
                    row.shapes = ""
        except Exception as e:  # noqa: BLE001 — telemetry must not break prod
            row.error = f"{type(e).__name__}: {e}"
            log.warning("kernel cost capture failed for %s: %s", name, e)
        with self._lock:
            prev = self._rows.get(name)
            row.captures = (prev.captures if prev else 0) + 1
            self._rows[name] = row
            self._seen_compiles[name] = compile_ledger.compiles_of(name)
        return row

    # ------------------------------------------------------------ queries

    def kernel_rows(self) -> dict[str, KernelCostRow]:
        with self._lock:
            return dict(self._rows)

    def reset(self) -> None:
        """Drop every captured row and the HBM latch (tests)."""
        with self._lock:
            self._rows.clear()
            self._seen_compiles.clear()
            self._hbm_state = None

    # ------------------------------------------------------------- export

    def export_to(self, counters) -> None:
        """Stamp every captured row into a Counters registry as
        ``jax.kernel.<fn>.<field>`` gauges (registered in
        monitor/names.py, documented in docs/Monitor.md). Values are
        process-wide, like the compile ledger's."""
        for name, row in self.kernel_rows().items():
            if row.error is not None:
                continue
            for fld in KernelCostRow.EXPORT_FIELDS:
                counters.set(f"jax.kernel.{name}.{fld}", getattr(row, fld))

    # ---------------------------------------------------------------- hbm

    def sample_hbm(self, counters=None) -> list[dict] | None:
        """Per-device ``memory_stats()`` rows, or None when the backend
        exposes none (CPU). With ``counters``, live/peak/limit bytes are
        also stamped as ``device.<i>.*`` gauges. The first all-None
        sample latches availability off so rebuild-edge sampling costs
        one flag test on CPU."""
        if self._hbm_state is False:
            return None
        try:
            import jax

            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 — backend down ≠ telemetry crash
            # do NOT latch: a transient init failure must not disable
            # HBM gauges for the process lifetime once the backend
            # recovers (review finding); the
            # permanent latch is reserved for backends that enumerate
            # fine and genuinely expose no memory_stats (CPU)
            return None
        rows: list[dict] = []
        any_stats = False
        any_errors = False
        for i, d in enumerate(devices):
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 — per-device degradation
                stats = None
                any_errors = True
            if not stats:
                continue
            any_stats = True
            in_use = int(stats.get("bytes_in_use", 0))
            peak = int(stats.get("peak_bytes_in_use", in_use))
            limit = int(stats.get("bytes_limit", 0))
            rows.append(
                {
                    "device": i,
                    "kind": getattr(d, "device_kind", d.platform),
                    "platform": d.platform,
                    "hbm_bytes_in_use": in_use,
                    "hbm_peak_bytes": peak,
                    "hbm_limit_bytes": limit,
                }
            )
            if counters is not None:
                counters.set(f"device.{i}.hbm_bytes_in_use", in_use)
                counters.set(f"device.{i}.hbm_peak_bytes", peak)
                counters.set(f"device.{i}.hbm_limit_bytes", limit)
        if not any_stats:
            if not any_errors:
                # every device answered "no stats" — the CPU shape:
                # latch off so later samples are one flag test
                self._hbm_state = False
            return None
        self._hbm_state = True
        return rows

    @property
    def hbm_available(self) -> bool | None:
        return self._hbm_state

    def hbm_in_use_mb(self) -> float | None:
        """Summed live HBM across local devices in MB, or None on
        backends without memory_stats — the soak watermark's sample
        (emulator/soak.py SoakConfig.hbm_slack_mb)."""
        rows = self.sample_hbm()
        if rows is None:
            return None
        return sum(r["hbm_bytes_in_use"] for r in rows) / 1e6


# ----------------------------------------------------------- pure joins


def efficiency_rows(
    rows: dict[str, KernelCostRow], snapshot: dict[str, float]
) -> list[dict]:
    """Join captured cost rows with measured span stats into achieved
    throughput: for each kernel whose ``span`` has a recorded
    ``profile.<span>_ms`` stat AND measures the work to completion
    (``span_complete``), compute GFLOP/s and GB/s against the span's
    p50 wall time. A completed span's wall includes host work
    (dispatch, transfer) around the kernel, so achieved numbers are
    honest lower bounds on device utilization; a dispatch-only span
    (async return, e.g. the sharded solve) reports its p50 but NO
    achieved rate — flops over dispatch wall would be unphysical.
    Pure function: feed it any snapshot (ctrl computes it
    server-side)."""
    out: list[dict] = []
    for name in sorted(rows):
        row = rows[name]
        d = row.to_jsonable()
        p50 = count = None
        if row.span:
            p50 = snapshot.get(f"profile.{row.span}_ms.p50")
            count = snapshot.get(f"profile.{row.span}_ms.count")
        d["span_p50_ms"] = p50
        d["span_count"] = int(count) if count else 0
        if row.span_complete and p50 and p50 > 0:
            sec = p50 / 1e3
            d["achieved_gflops"] = round(row.flops / sec / 1e9, 3)
            d["achieved_gbs"] = round(row.bytes_accessed / sec / 1e9, 3)
        else:
            d["achieved_gflops"] = None
            d["achieved_gbs"] = None
        out.append(d)
    return out


def shard_rows(arr) -> list[dict]:
    """Per-device shard layout of a sharded array from its Sharding
    metadata only — never ``shard.data`` (that dispatches a
    ``_multi_slice`` program: an XLA compile the steady-state gate
    would rightly flag, plus a device sync)."""
    try:
        sharding = arr.sharding
        shape = arr.shape
        itemsize = arr.dtype.itemsize
        shard_shape = sharding.shard_shape(shape)
        nbytes = itemsize
        for s in shard_shape:
            nbytes *= s
        rows = []
        for dev, idx in sharding.devices_indices_map(shape).items():
            index = [
                [
                    0 if sl.start is None else int(sl.start),
                    dim if sl.stop is None else int(sl.stop),
                ]
                for sl, dim in zip(idx, shape)
            ]
            rows.append(
                {
                    "device": dev.id,
                    "platform": dev.platform,
                    "index": index,
                    "shard_shape": list(shard_shape),
                    "shard_bytes": nbytes,
                }
            )
        rows.sort(key=lambda r: r["device"])
        return rows
    except Exception as e:  # noqa: BLE001 — metadata-only best effort
        log.debug("shard_rows unavailable: %s", e)
        return []


#: the process telemetry every consumer shares
_TELEMETRY = DeviceTelemetry()


def telemetry() -> DeviceTelemetry:
    return _TELEMETRY


def observe(
    name: str,
    lower,
    span: str | None = None,
    span_complete: bool = True,
) -> None:
    _TELEMETRY.observe(name, lower, span=span, span_complete=span_complete)


def capture(
    name: str,
    lower,
    span: str | None = None,
    span_complete: bool = True,
) -> KernelCostRow:
    return _TELEMETRY.capture(
        name, lower, span=span, span_complete=span_complete
    )


def kernel_rows() -> dict[str, KernelCostRow]:
    return _TELEMETRY.kernel_rows()


def export_to(counters) -> None:
    _TELEMETRY.export_to(counters)


def sample_hbm(counters=None) -> list[dict] | None:
    return _TELEMETRY.sample_hbm(counters)


def hbm_in_use_mb() -> float | None:
    return _TELEMETRY.hbm_in_use_mb()
