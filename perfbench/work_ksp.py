"""The least work of one area's KSP batch, from the graph and the paths
found alone.

The same count whatever implements the batch: not from padded tables, not
from a fixpoint's sweep count, not from the rounds a kernel dispatched. A
batch of J jobs (one a KSP prefix) from one root over an area of N nodes
and E directed edges, in which job j found p_j edge-disjoint paths, has
to, at the least,

  share the first round                  every job's path 1 comes from the
                                         root's distances, which the SPF
                                         solve before it has: nothing
  for each job, each round after it      read each edge's endpoint and
  (p_j - 1 of them: a path's links are   weight once (E * 8) and write each
  banned, the distances found again)     node's distance once (N * 4)
  write the paths                        one int32 a hop

bytes. The round that proves no further path exists is left out: the
degree of the root or of the destination can prove it for nothing. It does
about one addition and one comparison an edge a round, so against the
chip's peaks it is bound by bandwidth.
"""

from __future__ import annotations

from perfbench import work


def batch_counts(g, unicast: dict) -> list[dict]:
    """Per area of a `backbone_sites` graph: nodes, edges, and what the
    plain unicast table `unicast` shows of the area's batch: jobs (prefixes
    with a next hop in the area), paths (a next hop each) and hops (the
    labels a next hop pushes + 1). Read off the FIB as programmed."""
    areas = g.meta["areas"]
    out = [
        {"nodes": 0, "edges": int((g.meta["edge_area"] == a).sum()),
         "jobs": 0, "paths": 0, "hops": 0}
        for a in range(len(areas))
    ]
    for in_areas in g.meta["node_areas"]:
        for a in in_areas:
            out[a]["nodes"] += 1
    for nhs in unicast.values():
        for name in {nh[5] for nh in nhs}:
            out[areas.index(name)]["jobs"] += 1
        for nh in nhs:
            row = out[areas.index(nh[5])]
            row["paths"] += 1
            row["hops"] += len(nh[8]) + 1
    return out


def batch_least_bytes(area: dict) -> float:
    rounds = area["paths"] - area["jobs"]
    return rounds * (area["edges"] * 8 + area["nodes"] * 4) + area["hops"] * 4


def batch_least_ops(area: dict) -> float:
    return 2.0 * (area["paths"] - area["jobs"]) * area["edges"]


def roofline_share_pct(
    device_kind: str, areas: list[dict], kernel_s: float
) -> float:
    """The least time the chip could take for one event's batch over the
    time its kernel took an event, in per cent. An event re-solves the
    batch of the one area its circuit lies in, and the mix draws circuits
    from every area alike: the mean over the areas."""
    peak = work.peaks(device_kind)
    least_s = sum(
        max(batch_least_bytes(a) / peak["hbm_bytes_per_s"],
            batch_least_ops(a) / peak["int32_ops_per_s"])
        for a in areas
    ) / len(areas)
    return 100.0 * least_s / kernel_s
