"""Runtime JAX compile ledger: per-function compile counts + transfer
counters, exported through the existing Counters/Prometheus path.

The static rules (orlint OR008-OR010) catch recompile *hazards*; this
module observes the recompiles that actually happen. It hooks
``jax.config.jax_log_compiles`` — every XLA compilation logs one
"Compiling <fn> with global shapes and types ..." record from
``jax._src.interpreters.pxla`` — and parses the function name out, so a
steady-state system can assert the thing PAPER.md's determinism mandate
assumes and nothing previously checked: **after warmup, the jit cache
is hit on every solve**. A recompile under churn is a bug (a shape
leaked past the padding buckets, a static arg took a fresh value): it
stalls the rebuild for the whole compile, once per variant.

Three consumers:

  * **Counters export** — ``export_to(counters)`` stamps
    ``jax.compiles.<fn>`` per jitted function, ``jax.compiles.total``,
    and the transfer seam counters ``jax.transfers.host_reads`` /
    ``jax.transfers.host_bytes`` (recorded explicitly by the
    spf_backend materialization seams — the process-wide values ride
    each node's Counters into the Prometheus export; see
    docs/Monitor.md).
  * **Test sanitizer** — tests marked ``@pytest.mark.jit_steady_state``
    call :func:`mark_warm` after their warmup calls; the conftest
    fixture fails the test if any compile lands after the mark
    (tests/conftest.py, the compile-stability analogue of the PR 5
    asyncio sanitizer).
  * **Bench lanes** — bench.py splits per-stage first-call compile cost
    out of steady-state p50s, and the churn smoke (ci.sh) exits nonzero
    on any post-warmup steady-state compile.

The handler is process-global and idempotent to install; while
installed, the pxla logger's propagation is disabled so enabling
log_compiles does not spray WARNING lines over test/bench output (the
records still reach any handler attached directly to that logger).
"""

from __future__ import annotations

import logging
import re
import threading
from dataclasses import dataclass, field

#: the loggers jax_log_compiles raises to WARNING (jax 0.9.0, the
#: installed line): pxla carries the per-compile
#: "Compiling jit(<fn>) with global shapes ..." record the ledger
#: parses; dispatch carries the tracing/compile-time chatter and
#: compiler one line per persistent-cache hit or write. All have
#: propagation disabled while installed so enabling log_compiles does
#: not spray the test/bench output.
_COMPILE_LOGGER = "jax._src.interpreters.pxla"
_CHATTER_LOGGERS = (
    _COMPILE_LOGGER, "jax._src.dispatch", "jax._src.compiler",
)

# jax 0.9.0 names the module "jit(<fn>)" where 0.4.x wrote the bare
# "<fn>" — the pattern written for 0.4.x matched nothing on 0.9.0 and
# the ledger silently counted zero. It keys on <fn> either way, so the
# jax.compiles.<fn> counter names stay what docs/Monitor.md lists.
_COMPILE_RE = re.compile(
    r"Compiling (?:jit\()?([\w<>.\-]+)\)? with global shapes"
)


@dataclass
class LedgerSnapshot:
    """Immutable view of compile counts at a point in time."""

    per_fn: dict[str, int] = field(default_factory=dict)

    @property
    def total(self) -> int:
        return sum(self.per_fn.values())

    def delta(self, newer: "LedgerSnapshot") -> dict[str, int]:
        """{fn: new compiles} between self and `newer` (>=, per fn)."""
        out: dict[str, int] = {}
        for fn, n in newer.per_fn.items():
            d = n - self.per_fn.get(fn, 0)
            if d > 0:
                out[fn] = d
        return out


class _LedgerHandler(logging.Handler):
    def __init__(self, ledger: "CompileLedger"):
        super().__init__(level=logging.DEBUG)
        self._ledger = ledger

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # noqa: BLE001 — never break jax logging
            return
        m = _COMPILE_RE.search(msg)
        if m:
            self._ledger._record_compile(m.group(1))


class CompileLedger:
    """Process-wide compile/transfer accounting. Thread-safe: the
    logging handler may fire from any dispatch thread."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._compiles: dict[str, int] = {}
        self._warm: LedgerSnapshot | None = None
        self._handler: _LedgerHandler | None = None
        self._null: logging.NullHandler | None = None
        self._prev_log_compiles: bool | None = None
        self._prev_propagate: dict[str, bool] = {}
        self.host_reads = 0
        self.host_bytes = 0

    # ------------------------------------------------------------ install

    @property
    def installed(self) -> bool:
        return self._handler is not None

    def install(self) -> None:
        """Idempotent: enable jax_log_compiles and attach the parsing
        handler. Import of jax happens here, not at module import — the
        monitor package must stay importable with the backend down."""
        if self._handler is not None:
            return
        import jax

        self._prev_log_compiles = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        logger = logging.getLogger(_COMPILE_LOGGER)
        self._handler = _LedgerHandler(self)
        logger.addHandler(self._handler)
        if logger.level > logging.WARNING or logger.level == 0:
            logger.setLevel(logging.WARNING)
        # keep the (now chatty) compile records off stderr while we
        # consume them; restored on uninstall. The NullHandler matters:
        # a propagate=False logger with NO handler falls through to
        # logging.lastResort, which prints the bare message to stderr
        self._null = logging.NullHandler()
        for name in _CHATTER_LOGGERS:
            lg = logging.getLogger(name)
            self._prev_propagate[name] = lg.propagate
            lg.propagate = False
            lg.addHandler(self._null)

    def uninstall(self) -> None:
        if self._handler is None:
            return
        import jax

        logging.getLogger(_COMPILE_LOGGER).removeHandler(self._handler)
        for name, prev in self._prev_propagate.items():
            lg = logging.getLogger(name)
            lg.propagate = prev
            if self._null is not None:
                lg.removeHandler(self._null)
        self._prev_propagate = {}
        self._null = None
        if self._prev_log_compiles is not None:
            jax.config.update("jax_log_compiles", self._prev_log_compiles)
        self._handler = None

    # ----------------------------------------------------------- recording

    def _record_compile(self, fn: str) -> None:
        with self._lock:
            self._compiles[fn] = self._compiles.get(fn, 0) + 1

    def record_transfer(self, nbytes: int) -> None:
        """One device→host materialization at a transfer seam (the
        spf_backend np.asarray sites). Cheap enough to call
        unconditionally — two int adds against an actual transfer."""
        with self._lock:
            self.host_reads += 1
            self.host_bytes += int(nbytes)

    # ------------------------------------------------------------- queries

    def snapshot(self) -> LedgerSnapshot:
        with self._lock:
            return LedgerSnapshot(per_fn=dict(self._compiles))

    def compiles_of(self, fn: str) -> int:
        """Compile count of one jitted function (0 when never seen or
        the ledger is not installed) — the device-telemetry recapture
        trigger (monitor/device.py), cheap enough for hot paths."""
        with self._lock:
            return self._compiles.get(fn, 0)

    def mark_warm(self) -> None:
        """Declare warmup over: compiles after this point are
        steady-state violations (see compiles_since_warm)."""
        self._warm = self.snapshot()

    @property
    def warm_marked(self) -> bool:
        return self._warm is not None

    def reset_warm(self) -> None:
        self._warm = None

    def compiles_since_warm(self) -> dict[str, int]:
        """{fn: compiles since mark_warm()}; empty when never marked."""
        if self._warm is None:
            return {}
        return self._warm.delta(self.snapshot())

    # -------------------------------------------------------------- export

    def export_to(self, counters) -> None:
        """Stamp the ledger into a Counters registry (names registered
        in monitor/names.py; the jax.compiles.* family is documented in
        docs/Monitor.md). Values are process-wide — compilation is a
        process-global resource shared by every in-process node."""
        snap = self.snapshot()
        for fn, n in snap.per_fn.items():
            counters.set(f"jax.compiles.{fn}", n)
        counters.set("jax.compiles.total", snap.total)
        counters.set("jax.transfers.host_reads", self.host_reads)
        counters.set("jax.transfers.host_bytes", self.host_bytes)


#: the process ledger every consumer shares
_LEDGER = CompileLedger()


def ledger() -> CompileLedger:
    return _LEDGER


def install() -> CompileLedger:
    _LEDGER.install()
    return _LEDGER


def uninstall() -> None:
    _LEDGER.uninstall()


def mark_warm() -> None:
    """Module-level convenience for the test sanitizer contract: a
    ``@pytest.mark.jit_steady_state`` test calls this once its warmup
    calls are done; every compile after it fails the test."""
    _LEDGER.mark_warm()


def record_transfer(nbytes: int) -> None:
    _LEDGER.record_transfer(nbytes)


def compiles_of(fn: str) -> int:
    return _LEDGER.compiles_of(fn)


def export_to(counters) -> None:
    _LEDGER.export_to(counters)
