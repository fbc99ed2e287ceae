"""The KSP kernel's share of its roofline: the least time the chip could
take for one event's KSP batch (perfbench/work_ksp.py: bytes over the HBM
peak, from the graph and the paths in the last compared table) over the
kernel's device time per traced event. Returns nothing where the trace
holds no such kernel or the driver counted no batch.
args: {"kernels": [regex, ...]}."""

from __future__ import annotations

from perfbench import work_ksp
from perfbench.readers.trace_kernel_ms import kernel_seconds


def read(obs: dict, args: dict) -> float | None:
    total = kernel_seconds(obs, args["kernels"])
    areas = obs.get("work", {}).get("ksp_areas")
    if total is None or not areas:
        return None
    return work_ksp.roofline_share_pct(
        obs["device_kind"], areas, total / obs["traced_events"]
    )
