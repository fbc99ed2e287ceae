"""A solve kernel's share of its roofline: the least time the chip could
take for the graph's solve (perfbench/work.py, bytes over the HBM peak)
over the kernel's device time per traced event. Returns nothing where the
trace holds no such kernel. args: {"kernels": [regex, ...]}."""

from __future__ import annotations

from perfbench import work
from perfbench.readers.trace_kernel_ms import kernel_seconds


def read(obs: dict, args: dict) -> float | None:
    total = kernel_seconds(obs, args["kernels"])
    if total is None or "work" not in obs:
        return None
    w = obs["work"]
    return work.roofline_share_pct(
        obs["device_kind"], w["nodes"], w["edges"], w["batch"],
        w["out_bytes"], total / obs["traced_events"],
    )
