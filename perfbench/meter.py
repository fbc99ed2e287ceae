"""Compile and transfer accounting for a phase of a run.

A copy of `chip_smoke.py`'s `Meter` (kept here so that a later PR that
changes the smoke cannot change what the benchmark counts): XLA backend
compile requests and their seconds, persistent-cache hits and writes,
jitted-function compiles by name (the program's compile ledger) and
device->host bytes at the solver's transfer seams.
"""

from __future__ import annotations

import threading

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"


class Meter:
    def __init__(self):
        import jax

        from openr_tpu.monitor import compile_ledger

        self._lock = threading.Lock()
        self._compile_s = 0.0
        self._backend_compiles = 0
        self._cache_hits = 0
        self._cache_writes = 0
        self._ledger = compile_ledger.install()
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            with self._lock:
                self._compile_s += secs
                self._backend_compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == _CACHE_HIT_EVENT:
                self._cache_hits += 1
            elif event == _CACHE_WRITE_EVENT:
                self._cache_writes += 1

    def mark(self) -> dict:
        with self._lock:
            return {
                "compile_s": self._compile_s,
                "backend_compiles": self._backend_compiles,
                "cache_hits": self._cache_hits,
                "cache_writes": self._cache_writes,
                "fns": self._ledger.snapshot(),
                "fetched_bytes": self._ledger.host_bytes,
            }

    def since(self, mark: dict) -> dict:
        now = self.mark()
        fns = mark["fns"].delta(now["fns"])
        return {
            "compiles": sum(fns.values()),
            "compiled_fns": fns,
            "backend_compiles": (
                now["backend_compiles"] - mark["backend_compiles"]
            ),
            "compile_s": round(now["compile_s"] - mark["compile_s"], 3),
            "cache_hits": now["cache_hits"] - mark["cache_hits"],
            "cache_writes": now["cache_writes"] - mark["cache_writes"],
            "fetched_bytes": now["fetched_bytes"] - mark["fetched_bytes"],
        }
