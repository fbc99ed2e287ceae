"""The statistics a traffic file or a per-layer metric's file may name."""

from __future__ import annotations


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of nothing")
    pos = (len(s) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def statistic(stat: str, values: list[float], obs: dict) -> float | None:
    """The statistics a traffic file or a reader may name."""
    if stat == "window_per_event_ms":
        if not obs["events"]:
            return None
        return obs["window_s"] * 1e3 / obs["events"]
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    if stat.startswith("p") and stat[1:].isdigit():
        return percentile(values, int(stat[1:]) / 100.0)
    raise ValueError(f"unknown statistic {stat!r}")
