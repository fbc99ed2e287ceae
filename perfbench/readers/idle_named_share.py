"""The share of the device's idle time in which a span of the program
shows the host at work: a check of the spans' coverage, not a speed.

Inside `perfbench:window`, the device is idle wherever no op runs. Each
idle interval is split by overlap: the part that lies under a working
host span of the program (`spf:`, `decision:`, `fib:`, `kvstore:`) is
named, the rest is not (the harness building the next event, queue hops,
a sleeping loop). Spans that only wrap others or a sleeping timer
(`ENVELOPES`) name nothing: what they hold beyond their children is just
what no span explains. The metric is named idle time over all idle time,
in percent. `trace_reduce.reduce_events` gives a whole gap to one span,
and only if that span covers half of it; this reading has no such
threshold.

`obs` carries the reduced trace, not the spans, so the run's trace is read
again: the newest `.xplane.pb` under `.perfbench_trace/`, which the
harness wrote seconds before. Nothing to read (an untraced run, no device
plane, no window span) returns None. args: none."""

from __future__ import annotations

import re
from pathlib import Path

from perfbench import trace_reduce

PROGRAM_SPAN = re.compile(r"^(spf|decision|fib|kvstore):")
#: the rebuild coroutine, the loop waiting for the solver thread, the
#: solver call around its phases, the debounce timer asleep
ENVELOPES = frozenset({
    "decision:rebuild", "decision:compute_diff", "decision:compute_rib",
    "decision:debounce_wait",
})
TRACES = Path(__file__).resolve().parents[2] / ".perfbench_trace"


def named_idle_share(trace: dict) -> float | None:
    """Percent of device idle time inside the window under a working
    program span, from the plain structure `trace_reduce.extract` makes."""
    window = None
    spans: list[tuple[int, int]] = []
    devices = []
    for plane in trace["planes"]:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            devices.append(plane)
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == trace_reduce.WINDOW_SPAN:
                    window = (start, start + dur)
                elif PROGRAM_SPAN.match(name) and name not in ENVELOPES:
                    spans.append((start, start + dur))
    if window is None:
        return None
    lo, hi = window
    named = trace_reduce.clip(trace_reduce.union(spans), lo, hi)
    idle_ns = named_ns = 0
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = (lines.get(trace_reduce.OPS_LINE)
               or lines.get(trace_reduce.MODULES_LINE) or [])
        busy = trace_reduce.clip(
            trace_reduce.union([(s, s + d) for _n, s, d in ops]), lo, hi)
        if not busy:
            continue
        edges = [lo, *[t for iv in busy for t in iv], hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            idle_ns += ge - gs
            named_ns += sum(e - s for s, e in trace_reduce.clip(named, gs, ge))
    if not idle_ns:
        return None
    return 100.0 * named_ns / idle_ns


def read(obs: dict, args: dict) -> float | None:
    if not obs.get("trace") or not obs["trace"].get("devices"):
        return None
    found = sorted(TRACES.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    return named_idle_share(trace_reduce.extract(found[-1]))
