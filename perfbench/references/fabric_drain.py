"""The plain reference of `fabric_drain`: one ToR's tables when switches of
the fabric are drained by Open/R's node-overload bit.

Written from the configuration's `guarantees` (upstream's rule: `SpfSolver`
never expands an overloaded node that is not the source); it imports
nothing of `openr_tpu` and takes nothing the program made. The graph is a
`perfbench/topologies/fat_tree_drained.py` graph: the drained switches, as
published at the instant the table was taken, ride its `meta["drained"]`
(node ids). A drained switch keeps its adjacencies and metrics; the rule
is about who may pass through it:

  distances  for each source s in {root} and the root's neighbours, scipy's
             Dijkstra over the graph with every edge *out of* a drained
             node removed, except s's own: a drained switch is a
             destination and the first hop of its own traffic, never a
             transit hop of anybody else's
  next hops  next hops(d) = { n : metric(root, n) + dist_n(d) == dist_root(d) },
             and a drained neighbour n only where d == n
  no route   where dist_root(d) is infinite (every way to d passes a
             drained switch)

  unicast  loopback(d)  -> every such n, route metric dist_root(d)
  mpls     label(d)     -> the same set, SWAP label(d), or PHP where n == d

as `perfbench/reference.py`, whose plain forms these tables are in; with an
empty drained set they are that file's tables.

`control=True` breaks one stated guarantee: the overload bit is ignored, so
a drained switch carries transit traffic. On the fabric that is, for each
drained aggregation switch of a pod that is not the root's, that pod's
ToRs keeping the next hop of the dead plane (one loopback and one label a
ToR); a drained spine moves nothing, because its plane has others.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

from perfbench import topo
from perfbench.reference import AREA

#: the guarantee `tables(..., control=True)` breaks, as control.py reports it
CONTROL = (
    "drain broken: the overload bit is ignored (a drained switch carries "
    "transit)"
)


def distances(g: topo.Graph, sources: list[int], drained) -> np.ndarray:
    """[len(sources), n]: row i the distances from sources[i], no path
    passing through a drained node other than that source."""
    is_drained = np.zeros(g.n, dtype=bool)
    is_drained[np.fromiter(drained, np.int64, len(drained))] = True
    metric = g.metric.astype(np.float64)

    def from_(srcs: list[int], keep: np.ndarray) -> np.ndarray:
        m = csr_matrix(
            (metric[keep], (g.src[keep], g.dst[keep])), shape=(g.n, g.n)
        )
        return dijkstra(m, directed=True, indices=srcs)

    transit = ~is_drained[g.src]
    out = np.empty((len(sources), g.n))
    free = [i for i, s in enumerate(sources) if not is_drained[s]]
    if free:
        out[free] = from_([sources[i] for i in free], transit)
    for i, s in enumerate(sources):
        if is_drained[s]:
            out[i] = from_([s], transit | (g.src == s))[0]
    return out


def tables(
    g: topo.Graph, root: int, control: bool = False
) -> tuple[dict, dict]:
    """(unicast, mpls) tables of `root`. `control=True` is the control: no
    switch counts as drained."""
    drained = frozenset() if control else frozenset(g.meta["drained"])
    out = g.src == root
    nbrs = g.dst[out]
    order = np.argsort(nbrs, kind="stable")
    nbrs, w = nbrs[order], g.metric[out][order].astype(np.float64)
    dist = distances(g, [root, *nbrs.tolist()], drained)
    d_root = dist[0]
    # [n_nbrs, n]: neighbour is on a shortest path to the column's node
    on_path = (w[:, None] + dist[1:]) == d_root[None, :]
    on_path &= np.isfinite(d_root)[None, :]
    on_path[:, root] = False
    for slot, n in enumerate(nbrs.tolist()):
        if n in drained:
            toward_itself = on_path[slot, n]
            on_path[slot] = False
            on_path[slot, n] = toward_itself
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
