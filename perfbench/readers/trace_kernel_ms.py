"""Device time of some jitted entries per traced event, from the trace:
the sum of their programs' durations over the events the trace holds.
args: {"kernels": [regex, ...]} matched against the jitted entry's name."""

from __future__ import annotations

import re


def kernel_seconds(obs: dict, patterns: list[str]) -> float | None:
    trace = obs.get("trace")
    if not trace or not obs.get("traced_events"):
        return None
    total = sum(
        s for fn, s in trace["kernels_s"].items()
        if any(re.fullmatch(p, fn) for p in patterns)
    )
    return total or None


def read(obs: dict, args: dict) -> float | None:
    total = kernel_seconds(obs, args["kernels"])
    if total is None:
        return None
    return total * 1e3 / obs["traced_events"]
