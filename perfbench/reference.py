"""The plain reference: one router's routing tables from a `topo.Graph`.

Independent of the program: it imports nothing of `openr_tpu` and takes
nothing the program made. Semantics (Open/R Decision, SP_ECMP, one area,
no overloads, no adjacency labels — what the benchmark's configurations
state): for every other reachable node d, with dist(.) the shortest-path
distance over the directed metrics,

  next hops(d) = { neighbour n of the root : metric(root, n) + dist(n, d)
                   == dist(root, d) }

  unicast  loopback(d)  -> every such n, route metric dist(root, d)
  mpls     label(d)     -> the same set, SWAP label(d), or PHP where n == d

Distances come from scipy's Dijkstra (float64: exact for these integer
metrics), one run from the root and one from each of its neighbours.

Plain forms, shared with `compare.py`:
  next hop = (neighbor_node, address, if_name, metric, weight, area,
              mpls_action, swap_label, push_labels)
  table    = {key: tuple(sorted(next hops))}, key a prefix string or label
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

from perfbench import topo

AREA = "0"


def distances(g: topo.Graph, sources: list[int]) -> np.ndarray:
    m = csr_matrix(
        (g.metric.astype(np.float64), (g.src, g.dst)), shape=(g.n, g.n)
    )
    return dijkstra(m, directed=True, indices=sources)


def tables(
    g: topo.Graph, root: int, ecmp: bool = True
) -> tuple[dict, dict]:
    """(unicast, mpls) tables of `root`. `ecmp=False` is the control: it
    breaks the configuration's ECMP guarantee by keeping one next hop (the
    lowest-numbered neighbour) where several tie."""
    out = g.src == root
    nbrs = g.dst[out]
    order = np.argsort(nbrs, kind="stable")
    nbrs, w = nbrs[order], g.metric[out][order].astype(np.float64)
    dist = distances(g, [root, *nbrs.tolist()])
    d_root = dist[0]
    # [n_nbrs, n]: neighbour is on a shortest path to the column's node
    on_path = (w[:, None] + dist[1:]) == d_root[None, :]
    on_path &= np.isfinite(d_root)[None, :]
    on_path[:, root] = False
    if not ecmp:
        first = on_path.argmax(axis=0)
        keep = np.zeros_like(on_path)
        keep[first, np.arange(g.n)] = on_path.any(axis=0)
        on_path = keep
    names = [topo.node_name(int(n)) for n in nbrs]
    ifs = [topo.if_name(root, int(n)) for n in nbrs]
    unicast: dict = {}
    mpls: dict = {}
    # destinations that share (next-hop set, distance) share their
    # unicast next hops: build each distinct tuple once
    memo: dict = {}
    cols = np.flatnonzero(on_path.any(axis=0))
    packed = np.packbits(on_path[:, cols], axis=0).T  # [dests, bytes]
    for row, d in zip(packed, cols.tolist()):
        metric = int(d_root[d])
        key = (row.tobytes(), metric)
        got = memo.get(key)
        if got is None:
            slots = np.flatnonzero(on_path[:, d]).tolist()
            got = memo[key] = (
                slots,
                tuple(sorted(
                    (names[s], names[s], ifs[s], metric, 0, AREA,
                     None, None, ())
                    for s in slots
                )),
            )
        slots, nhs = got
        unicast[topo.loopback(d)] = nhs
        label = topo.node_label(d)
        mpls[label] = tuple(sorted(
            (names[s], names[s], ifs[s], metric, 0, AREA,
             *(("PHP", None) if int(nbrs[s]) == d else ("SWAP", label)), ())
            for s in slots
        ))
    return unicast, mpls
