"""The plain reference of `backbone_ksp`: one border router's tables when
every loopback is KSP2_ED_ECMP / SR_MPLS and the LSDB is split into areas.

Written from the configuration's `guarantees` and docs/Decision.md; it
imports nothing of `openr_tpu` and takes nothing the program made. The
graph is a `perfbench/topologies/backbone_sites.py` graph: areas, names
and the areas a router sits in ride its `meta`.

Per area the root sits in, over that area's edges alone:

  unicast  for every loopback another router of the area advertises, up
           to K = 16 edge-disjoint shortest paths from the root, found by
           successive pruning: the shortest path, its links banned in both
           directions, Dijkstra again, until K paths or none is left. Each
           path is one next hop: the path's first link, metric = the path's
           cost, and a PUSH of the node labels of the hops after the first,
           destination included, bottom of the stack first (none where the
           destination is the neighbour).
  mpls     label(d) by SP_ECMP as `perfbench/reference.py` makes them: every
           neighbour on a shortest path, SWAP label(d), PHP at d itself.

Then the fold across areas, areas in sorted order: a prefix or label that
two areas hold goes to the lower IGP cost (the metric keys are equal here:
every loopback is advertised with the default metrics); at equal cost the
next hops are the union.

Where this follows the program and not the description (each found by
reading `decision/ksp.py`'s docstrings, none by calling it):
  * the tie rule walks from the destination back to the root and takes,
    at each step, the predecessor with the smallest router name among
    those on a shortest path (`extract_path`'s docstring); walking forward
    from the root under the same words gives other paths;
  * the label stack holds the destination's own label too ("interior
    hops" in the guarantee reads as every hop after the first);
  * a route's IGP cost in the fold is its cheapest next hop's metric.

`control=True` breaks one stated guarantee, edge-disjointness: path 2 may
reuse every link of path 1 but the first (only that one is banned before
the second Dijkstra; from path 3 on all links of the paths before are
banned again).
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

from perfbench import topo

K = 16

#: the guarantee `tables(..., control=True)` breaks, as control.py reports it
CONTROL = "edge-disjointness broken (path 2 may reuse a link of path 1)"


class _Area:
    """One area's edges as the walk needs them."""

    def __init__(self, g: topo.Graph, a: int):
        keep = g.meta["edge_area"] == a
        self.src = g.src[keep]
        self.dst = g.dst[keep]
        self.metric = g.metric[keep].astype(np.float64)
        self.n = g.n
        self.name = g.meta["areas"][a]
        # in-edges of a node, as (predecessor's name, predecessor, edge):
        # sorted, so that the first that fits is the smallest name
        names = g.meta["names"]
        self.into: list[list] = [[] for _ in range(g.n)]
        for e, (u, v) in enumerate(zip(self.src.tolist(), self.dst.tolist())):
            self.into[v].append((names[u], u, e))
        for row in self.into:
            row.sort()
        self.slot = {
            (u, v): e
            for e, (u, v) in enumerate(zip(self.src.tolist(), self.dst.tolist()))
        }

    def distances(self, root: int, banned: np.ndarray | None) -> np.ndarray:
        keep = slice(None) if banned is None else ~banned
        m = csr_matrix(
            (self.metric[keep], (self.src[keep], self.dst[keep])),
            shape=(self.n, self.n),
        )
        return dijkstra(m, directed=True, indices=root)

    def walk_back(self, dist, root: int, dest: int, banned) -> list[int] | None:
        """root..dest along shortest-path links, smallest-name predecessor
        at every tie; None where the destination is out of reach."""
        if not np.isfinite(dist[dest]):
            return None
        path = [dest]
        v = dest
        while v != root:
            for _name, u, e in self.into[v]:
                if banned[e]:
                    continue
                if dist[u] + self.metric[e] == dist[v]:
                    path.append(u)
                    v = u
                    break
            else:
                return None
        path.reverse()
        return path

    def ban(self, banned: np.ndarray, links) -> None:
        for u, v in links:
            banned[self.slot[(u, v)]] = True
            banned[self.slot[(v, u)]] = True


def _ksp_paths(area: _Area, dist0, root: int, dest: int, control: bool):
    """[(cost, [root..dest])] for one destination."""
    out = []
    banned = np.zeros(area.src.shape[0], bool)
    held: list = []  # control: links of path 1 not banned yet
    dist = dist0
    for _ in range(K):
        path = area.walk_back(dist, root, dest, banned)
        if path is None:
            break
        out.append((int(dist[dest]), path))
        links = list(zip(path, path[1:]))
        area.ban(banned, held)
        held = []
        if control and len(out) == 1:
            links, held = links[:1], links[1:]
        area.ban(banned, links)
        dist = area.distances(root, banned)
    return out


def _area_tables(g: topo.Graph, a: int, root: int, control: bool):
    """(unicast, mpls, igp by key) of one area, plain forms."""
    area = _Area(g, a)
    names = g.meta["names"]
    dist0 = area.distances(root, None)
    unicast: dict = {}
    for d in range(g.n):
        if d == root or a not in g.meta["node_areas"][d]:
            continue
        nhs = []
        for cost, path in _ksp_paths(area, dist0, root, d, control):
            first = path[1]
            stack = tuple(reversed([topo.node_label(n) for n in path[2:]]))
            nhs.append((
                names[first], names[first], topo.if_name(root, first), cost,
                0, area.name, "PUSH" if stack else None, None, stack,
            ))
        if nhs:
            unicast[topo.loopback(d)] = tuple(sorted(set(nhs)))
    # label routes, SP_ECMP: a neighbour n is a next hop to d where
    # metric(root, n) + dist(n, d) == dist(root, d)
    out = area.src == root
    mpls: dict = {}
    nbrs = area.dst[out].tolist()
    w = area.metric[out].tolist()
    from_nbr = {n: area.distances(n, None) for n in nbrs}
    for d in range(g.n):
        if d == root or not np.isfinite(dist0[d]):
            continue
        if a not in g.meta["node_areas"][d]:
            continue
        label = topo.node_label(d)
        nhs = [
            (names[n], names[n], topo.if_name(root, n), int(dist0[d]), 0,
             area.name, *(("PHP", None) if n == d else ("SWAP", label)), ())
            for n, wn in zip(nbrs, w) if wn + from_nbr[n][d] == dist0[d]
        ]
        if nhs:
            mpls[label] = tuple(sorted(nhs))
    return unicast, mpls


def _fold(tables: list[dict]) -> dict:
    """Areas in sorted order: lower IGP cost (the cheapest next hop's
    metric) wins, equal cost unions the next hops."""
    out: dict = {}
    for table in tables:
        for key, nhs in table.items():
            cur = out.get(key)
            if cur is None:
                out[key] = nhs
                continue
            mine, theirs = min(nh[3] for nh in cur), min(nh[3] for nh in nhs)
            if theirs < mine:
                out[key] = nhs
            elif theirs == mine:
                out[key] = tuple(sorted(set(cur) | set(nhs)))
    return out


def tables(
    g: topo.Graph, root: int, control: bool = False
) -> tuple[dict, dict]:
    """(unicast, mpls) tables of `root`; `control=True`: see `CONTROL`."""
    order = sorted(
        g.meta["node_areas"][root], key=lambda a: g.meta["areas"][a])
    per_area = [_area_tables(g, a, root, control) for a in order]
    return _fold([u for u, _m in per_area]), _fold([m for _u, m in per_area])
