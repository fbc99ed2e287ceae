"""The least work of one batched shortest-path solve, from the graph alone.

The same count whatever implements the solve: not from padded tables, not
from a sweep count. One solve of B sources over a graph of N nodes and E
directed edges has to, at the least,

  relax each edge once per source        read one distance      E * B * 4
  read each edge's endpoint and weight   once                   E * 8
  read and write each distance once                             2 * N * B * 4
  write the packed result the host fetches                      out_bytes

bytes (int32 distances, ids and weights). It does E*B additions and E*B
comparisons, so against the chip's peaks it is bound by bandwidth.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def solve_least_bytes(nodes: int, edges: int, batch: int, out_bytes: float) -> float:
    return edges * batch * 4 + edges * 8 + 2 * nodes * batch * 4 + out_bytes


def solve_least_ops(edges: int, batch: int) -> float:
    return 2.0 * edges * batch


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name} "
            f"(have {sorted(table)}): add the device with its source"
        )
    return table[device_kind]


def roofline_share_pct(
    device_kind: str, nodes: int, edges: int, batch: int, out_bytes: float,
    kernel_s: float,
) -> float:
    """The least time the chip could take for one solve over the time its
    kernel took, in per cent. Bandwidth-bound: the byte term is the larger."""
    peak = peaks(device_kind)
    least_s = max(
        solve_least_bytes(nodes, edges, batch, out_bytes) / peak["hbm_bytes_per_s"],
        solve_least_ops(edges, batch) / peak["int32_ops_per_s"],
    )
    return 100.0 * least_s / kernel_s
