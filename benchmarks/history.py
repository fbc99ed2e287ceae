"""Bench-history sentinel: an append-only trajectory of bench.py runs.

The bench trajectory has so far been point-in-time JSON artifacts
(BENCH_r0x.json) committed by hand — there is no machine-readable
history a regression check can read. This module gives every
``bench.py`` run a one-line JSONL record in ``BENCH_HISTORY.jsonl``:

  * the bench's emitted row (metric/value/detail) verbatim,
  * the compile-ledger per-fn snapshot and the device-telemetry kernel
    cost rows (docs/Monitor.md "Device telemetry") at end of run,
  * a **host fingerprint** (platform / machine / python / jax /
    backend / cpu count) — comparisons only ever happen between runs
    with the SAME fingerprint, because a CPU-fallback laptop row and a
    real-TPU row are different experiments.

``--check`` compares the newest row's headline metrics against the
median of all PRIOR same-fingerprint rows and flags >25% regressions
(latency metrics up, throughput metrics down). The ci.sh lane runs it
warn-only: bench variance on burstable CI hosts is real, so the
sentinel's job is to make a drifting trajectory loud, not to block.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
HISTORY_PATH = REPO_ROOT / "BENCH_HISTORY.jsonl"

#: headline metrics compared by --check: name -> direction
#: ("lower" = regression when the value RISES, "higher" = when it falls)
HEADLINE_METRICS: dict[str, str] = {
    "value": "lower",  # the headline solve p50 (ms)
    "convergence_p50_ms": "lower",
    "prefix_churn_p50_ms": "lower",
    "topo_churn_p50_ms": "lower",
    "prefix_routes_per_sec": "higher",
    # steady-state work ledger (docs/Monitor.md "Work ledger"): a rising
    # touched/delta ratio on a delta-proportional stage means someone
    # reintroduced a full-table walk. merge and redistribute are
    # delta-native since ISSUE 17 (delta merge book + redistribution
    # entry books; BENCH_WORK_r02.json pins the baseline — ratios ~2
    # and ~1 instead of the r01-era ~10^4), so their ratios no longer
    # drift with table size: ANY sustained rise here is a reintroduced
    # O(routes) walk and trips the sentinel
    "work_merge_ratio": "lower",
    "work_redistribute_ratio": "lower",
    "work_election_ratio": "lower",
    "work_fib_ratio": "lower",
}

DEFAULT_TOLERANCE = 0.25


def host_fingerprint() -> dict:
    """The same-host / same-backend identity comparisons key on.
    Node name is included deliberately: two hosts with identical specs
    still have different background load profiles."""
    import platform

    fp = {
        "node": platform.node(),
        "machine": platform.machine(),
        "system": platform.system(),
        "python": platform.python_version(),
        "cpus": os.cpu_count(),
    }
    try:
        import jax

        fp["jax"] = jax.__version__
        fp["backend"] = jax.default_backend()
    except Exception:  # noqa: BLE001 — fingerprint works without a backend
        fp["jax"] = None
        fp["backend"] = None
    return fp


def fingerprint_key(fp: dict) -> str:
    import hashlib

    blob = json.dumps(fp, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def append_row(
    row: dict,
    compiles: dict | None = None,
    kernel_cost: dict | None = None,
    path: Path | str | None = None,
) -> dict:
    """Append one bench run's record; returns the record. Best-effort
    caller contract: bench.py wraps this in try/except so a read-only
    checkout can never fail a measurement."""
    p = Path(path) if path is not None else HISTORY_PATH
    fp = host_fingerprint()
    rec = {
        "ts": time.time(),
        "fingerprint": fp,
        "fp_key": fingerprint_key(fp),
        "row": row,
        "compiles": compiles or {},
        "kernel_cost": kernel_cost or {},
    }
    with open(p, "a") as f:
        f.write(json.dumps(rec, default=str) + "\n")
    return rec


def load_history(path: Path | str | None = None) -> list[dict]:
    p = Path(path) if path is not None else HISTORY_PATH
    if not p.exists():
        return []
    out = []
    with open(p) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # a torn tail line must not kill the check
    return out


def _median(vals: list[float]) -> float:
    # the shared exact nearest-rank percentile (monitor/fleet.py) —
    # the one definition flood_trace / convergence / fleet tables use
    from openr_tpu.monitor.fleet import percentile

    return percentile(vals, 0.5)


def _metric_value(rec: dict, metric: str) -> float | None:
    v = rec.get("row", {}).get(metric)
    if isinstance(v, (int, float)) and v == v:  # non-None, non-NaN
        return float(v)
    return None


def check_history(
    records: list[dict], tolerance: float = DEFAULT_TOLERANCE
) -> list[str]:
    """Compare the NEWEST record's headline metrics vs the median of all
    prior records sharing its fingerprint AND metric name (a row under
    another metric name never gates this one). Returns human-readable
    warnings; empty = clean. Pure over
    the loaded records — testable without files."""
    if len(records) < 2:
        return []
    latest = records[-1]
    key = latest.get("fp_key")
    name = latest.get("row", {}).get("metric")
    prior = [
        r
        for r in records[:-1]
        if r.get("fp_key") == key and r.get("row", {}).get("metric") == name
    ]
    if not prior:
        return []
    warnings: list[str] = []
    for metric, direction in HEADLINE_METRICS.items():
        cur = _metric_value(latest, metric)
        if cur is None:
            continue
        base_vals = [
            v
            for v in (_metric_value(r, metric) for r in prior)
            if v is not None
        ]
        if not base_vals:
            continue
        base = _median(base_vals)
        if base <= 0:
            continue
        ratio = cur / base
        regressed = (
            ratio > 1 + tolerance
            if direction == "lower"
            else ratio < 1 - tolerance
        )
        if regressed:
            warnings.append(
                f"{metric}: {cur:g} vs median {base:g} of {len(base_vals)} "
                f"prior same-fingerprint run(s) "
                f"({(ratio - 1) * 100:+.1f}%, tolerance "
                f"{tolerance * 100:.0f}%)"
            )
    return warnings


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--check",
        action="store_true",
        help="compare the newest row vs prior same-fingerprint medians",
    )
    ap.add_argument("--path", default=None, help="history file override")
    ap.add_argument(
        "--tolerance", type=float, default=DEFAULT_TOLERANCE,
        help="relative regression threshold (default 0.25)",
    )
    ap.add_argument(
        "--strict",
        action="store_true",
        help="exit 2 on regression (default: warn-only, exit 0)",
    )
    args = ap.parse_args(argv)
    if not args.check:
        ap.print_help()
        return 0
    records = load_history(args.path)
    if len(records) < 2:
        print(
            f"bench-history: {len(records)} record(s) — nothing to "
            "compare yet"
        )
        return 0
    warnings = check_history(records, tolerance=args.tolerance)
    if not warnings:
        fp = records[-1].get("fp_key", "?")
        print(
            f"bench-history: newest row within tolerance "
            f"({len(records)} records, fingerprint {fp})"
        )
        return 0
    for w in warnings:
        print(f"bench-history REGRESSION: {w}", file=sys.stderr)
    return 2 if args.strict else 0


if __name__ == "__main__":
    sys.exit(main())
