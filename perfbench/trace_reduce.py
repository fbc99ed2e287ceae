"""From a profiler trace to device busy/idle, kernel times and idle gaps.

`extract` reads an `.xplane.pb` (with `jax.profiler.ProfileData`, nothing
but JAX) into a plain structure, `reduce_events` turns that structure into
numbers. The split lets the test run the reduction on a recorded cut kept
as JSON (`tests/perfbench/data/`), and lets a reviewer read how every
device number of the benchmark is made.

Plain structure:
  {"planes": [{"name": str, "lines": [{"name": str,
               "events": [[name, start_ns, duration_ns], ...]}]}]}

What is read, on a TPU trace:
  device planes   "/device:TPU:<n>"
  "XLA Ops"       one event per executed HLO op (a loop's event spans its
                  body's): the union of their intervals is when the device
                  was busy; an op's own time is its event less its children
  "XLA Modules"   one event per executed program, named after the jitted
                  entry ("jit_<fn>(<fingerprint>)"): a kernel's time is the
                  sum of its programs' durations
  host planes     `TraceAnnotation`s of the program ("spf:...") and the
                  harness's own "perfbench:window", which bounds the traced
                  part of the window on the trace's clock
"""

from __future__ import annotations

import re
from pathlib import Path

WINDOW_SPAN = "perfbench:window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: gaps shorter than this lie between two ops of one program: they are
#: summed under one name and not looked up among the host's spans
SHORT_GAP_NS = 20_000
SHORT_GAPS = "device: between ops"
#: host spans that may own an idle gap: the program's annotations
HOST_SPAN = re.compile(r"^(spf|decision|fib|kvstore|perfbench):")


def extract(path: Path, keep_host=HOST_SPAN) -> dict:
    """The trace as plain data: every device line, and of the host planes
    only the annotations (the host tracer records far more)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [
                [op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if is_device or keep_host.match(ev.name)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(name: str) -> str:
    """An op event carries its whole HLO line: "%while.3 = (s32[...".
    The op's own name is enough, and keeps a recorded cut small."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events: list) -> dict[str, int]:
    """Nanoseconds by name with every event's nested children taken out
    (a while loop's event spans its body's ops, which have events of their
    own on the same line)."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, ns = stack.pop()
            out[name] = out.get(name, 0) + ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, merged [start, end) intervals."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def module_fn(name: str) -> str:
    """"jit_batched_sssp_split_rib(1234...)" -> "batched_sssp_split_rib"."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def reduce_events(trace: dict) -> dict:
    """busy_s, window_s, kernel seconds by jitted entry, the device ops and
    the idle gaps that took most time. Seconds, averaged over the device
    planes that ran anything."""
    host_spans: list[tuple[str, int, int]] = []
    window = None
    devices = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            devices.append(plane)
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    window = (start, start + dur)
                elif HOST_SPAN.match(name):
                    host_spans.append((name, start, start + dur))
    busy_ns: list[int] = []
    kernels: dict[str, float] = {}
    kernel_calls: dict[str, int] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    all_events = [
        (s, s + d) for p in devices for ln in p["lines"]
        for _n, s, d in ln["events"]
    ]
    if window is None and all_events:
        window = (min(s for s, _ in all_events), max(e for _, e in all_events))
    if window is None:
        return {"busy_s": 0.0, "window_s": 0.0, "kernels_s": {},
                "kernel_calls": {}, "device_ops": [], "idle_gaps": [],
                "devices": 0}
    lo, hi = window
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = clip(union([(s, s + d) for _n, s, d in op_events]), lo, hi)
        if not busy:
            continue
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, d in lines.get(MODULES_LINE, []):
            if s >= lo and s + d <= hi:
                fn = module_fn(name)
                kernels[fn] = kernels.get(fn, 0.0) + d / 1e9
                kernel_calls[fn] = kernel_calls.get(fn, 0) + 1
        inside = [e for e in lines.get(OPS_LINE, [])
                  if e[1] >= lo and e[1] + e[2] <= hi]
        for name, ns in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9
        # idle gaps, each given to the host span that covers most of it
        edges = [lo, *[t for iv in busy for t in iv], hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            if ge - gs < SHORT_GAP_NS:
                gaps[SHORT_GAPS] = gaps.get(SHORT_GAPS, 0.0) + (ge - gs) / 1e9
                continue
            owner, best = "host: other", 0
            for name, s, e in host_spans:
                cover = min(e, ge) - max(s, gs)
                if cover > best:
                    owner, best = name, cover
            if best * 2 < ge - gs:
                owner = "host: other"
            gaps[owner] = gaps.get(owner, 0.0) + (ge - gs) / 1e9
    n = max(len(busy_ns), 1)

    def top(d: dict) -> list:
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]

    return {
        "busy_s": sum(busy_ns) / 1e9 / n,
        "window_s": (hi - lo) / 1e9,
        "kernels_s": {k: v / n for k, v in kernels.items()},
        "kernel_calls": {k: v // n for k, v in kernel_calls.items()},
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
        "devices": len(busy_ns),
    }


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: Path) -> dict:
    return reduce_events(extract(find_xplane(trace_dir)))
