"""The plain reference of `fabric_linkfail`: one ToR's tables when links of
the fabric are down.

Written from the configuration's `guarantees`; it imports nothing of
`openr_tpu` and takes nothing the program made. The graph is a
`perfbench/topologies/fat_tree_failed.py` graph: every link of the fabric
as arrays, and under `meta["failed"]` the links (u, v) down at the instant
the table was taken. A link that is down carries nothing in either
direction: its adjacency is withdrawn at both ends. So the tables are
`perfbench/reference.py`'s over the graph less both directed edges of
every failed link; with an empty failed set they are that file's tables.

`control=True` breaks one stated guarantee: a failed link carries routes.
The failed set is ignored and the tables are those of the whole fabric.
On the fabric that is, for each ToR<->agg link down in a pod that is not
the root's, that ToR keeping the next hop through the dead uplink's plane
(one loopback and one label); an agg<->spine link down moves nothing,
because its plane has others.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference, topo

#: the guarantee `tables(..., control=True)` breaks, as control.py reports it
CONTROL = "link failure broken: the failed links are kept in the graph"


def without(g: topo.Graph, failed) -> topo.Graph:
    """`g` less both directed edges of every link in `failed`."""
    if not failed:
        return g
    links = np.sort(np.array(sorted(failed), dtype=np.int64).reshape(-1, 2), axis=1)
    lo, hi = np.minimum(g.src, g.dst), np.maximum(g.src, g.dst)
    gone = np.isin(lo * g.n + hi, links[:, 0] * g.n + links[:, 1])
    keep = ~gone
    return topo.Graph(g.n, g.src[keep], g.dst[keep], g.metric[keep], g.meta)


def tables(
    g: topo.Graph, root: int, control: bool = False
) -> tuple[dict, dict]:
    """(unicast, mpls) tables of `root`. `control=True` is the control:
    no link counts as failed."""
    failed = frozenset() if control else frozenset(g.meta["failed"])
    return reference.tables(without(g, failed), root)
