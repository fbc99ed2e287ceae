"""The plain reference of `backbone_lfa`: one border router's tables when
every loopback is SP_ECMP / IP with RFC 5286 loop-free alternates and the
LSDB is split into areas.

Written from the configuration's `guarantees` and RFC 5286 section 3.1
(inequality 1, link protection); it imports nothing of `openr_tpu` and
takes nothing the program made. The graph is a
`perfbench/topologies/backbone_sites.py` graph: areas, names and the areas
a router sits in ride its `meta`; `meta["overloaded"]` (node ids), where a
test sets it, are routers with the overload bit (none in the
configuration).

Per area the root S sits in, over that area's edges alone, dist(.) is
scipy's Dijkstra from S and from each of its neighbours in the area, no
path passing through an overloaded router other than its source (the
no-transit rule of `references/fabric_drain.py`, whose `distances` this
calls):

  unicast  loopback(d) -> every neighbour n with metric(S, n) + dist_n(d)
           == dist_S(d), an overloaded n only where n == d; route metric
           dist_S(d)
  mpls     label(d) -> the same set, SWAP label(d), PHP where n == d
  backups  backup_key(loopback(d)) -> every neighbour N of S in the area
           that is no primary of loopback(d), with
               dist_N(d) < dist_N(S) + dist_S(d)        (strictly)
           and N not overloaded unless N == d: one next hop a least-metric
           link S -> N, metric = metric(S, N) + dist_N(d), no MPLS action;
           the key is there only where the route has one or more backups

Then the fold across areas, areas in sorted order: a loopback that two
areas hold goes to the lower IGP cost (dist_S(d); the metric keys are
equal: every loopback is advertised with the default metrics) with its
backups; at equal cost the primaries are the union and the backups are
the union of both areas' backups less every (neighbour, interface) that is
a primary of the merged route, a repeat at the least metric (the earlier
area's at equal metric). Labels: lower IGP cost, equal cost the union.

The backups ride the unicast table under keys of their own, so that the
harness's comparison (`run.check_tables`) and its control
(`control.py`) read them with the routes, unchanged: a driver puts the
program's backups under the same keys (`drivers/decision_fib_lfa.py`).

Where this follows the program's docstrings and not the RFC (each found
by reading docs/Decision.md and `spf_backend._lfa_backups`'s
docstring, none by calling it):
  * "N is not a primary next hop" is read per area, before the fold: the
    area's own first hops of the route;
  * a backup's metric is metric(S, N) + dist_N(d) (the RFC defines the
    condition, not a metric for the alternate), metric(S, N) the least
    over parallel links, one next hop a least-metric link (the backbone
    has no parallel links);
  * an overloaded N is excluded unless it is the destination (RFC 5286
    section 3.5 excludes it as a transit router; the exception is
    Open/R's), and overloaded routers carry no transit in any dist;
  * the fold at a tie (the RFC knows no areas) is docs/Decision.md's
    "LFA backups across areas";
  * label routes get no backups; a KSP prefix would get none (there are
    none here).

`control=True` breaks one stated guarantee, the strict inequality: with
dist_N(d) <= dist_N(S) + dist_S(d) a neighbour whose shortest path to d
ties with the one back through S is taken as a backup.
"""

from __future__ import annotations

import numpy as np

from perfbench import topo
from perfbench.references.fabric_drain import distances

#: the guarantee `tables(..., control=True)` breaks, as control.py reports it
CONTROL = (
    "the strict inequality broken (<=: a neighbour whose path ties back "
    "through the root is a backup)"
)


def backup_key(prefix: str) -> str:
    """The unicast table's key of a route's backups."""
    return f"{prefix} backups"


def _area_tables(g: topo.Graph, a: int, root: int, control: bool):
    """({loopback: (igp, primaries, backups)}, {label: nexthops}) of one
    area, plain forms."""
    keep = g.meta["edge_area"] == a
    sub = topo.Graph(g.n, g.src[keep], g.dst[keep], g.metric[keep])
    area, names = g.meta["areas"][a], g.meta["names"]
    over = np.zeros(g.n, dtype=bool)
    over[np.fromiter(g.meta.get("overloaded", ()), np.int64)] = True
    out = sub.src == root
    link: dict[int, float] = {}
    for n, w in zip(sub.dst[out].tolist(), sub.metric[out].tolist()):
        link[n] = min(link.get(n, np.inf), float(w))
    nbrs = sorted(link)
    dist = distances(
        sub, [root, *nbrs], frozenset(np.flatnonzero(over).tolist()))
    d_root, d_nbr = dist[0], dist[1:]
    w = np.array([link[n] for n in nbrs])
    # [n_nbrs, n]: neighbour n is a primary first hop toward the column
    on_path = (w[:, None] + d_nbr) == d_root[None, :]
    on_path &= np.isfinite(d_root)[None, :]
    on_path[:, root] = False
    back = d_nbr < (d_nbr[:, [root]] + d_root[None, :])
    if control:
        back = d_nbr <= (d_nbr[:, [root]] + d_root[None, :])
    back &= np.isfinite(d_root)[None, :] & np.isfinite(d_nbr)
    back &= np.isfinite(d_nbr[:, [root]])
    for slot, n in enumerate(nbrs):
        if over[n]:
            on_path[slot] = on_path[slot] & (np.arange(g.n) == n)
            back[slot] = back[slot] & (np.arange(g.n) == n)
    back &= ~on_path
    back[:, root] = False

    def nexthop(slot: int, metric: int, action=(None, None)) -> tuple:
        n = nbrs[slot]
        return (names[n], names[n], topo.if_name(root, n), metric, 0, area,
                *action, ())

    unicast: dict = {}
    mpls: dict = {}
    for d in range(g.n):
        if d == root or a not in g.meta["node_areas"][d]:
            continue
        slots = np.flatnonzero(on_path[:, d]).tolist()
        if not slots:
            continue
        igp = int(d_root[d])
        primaries = tuple(sorted(nexthop(s, igp) for s in slots))
        backups = tuple(sorted(
            nexthop(s, int(w[s] + d_nbr[s, d]))
            for s in np.flatnonzero(back[:, d]).tolist()
        ))
        unicast[topo.loopback(d)] = (igp, primaries, backups)
        label = topo.node_label(d)
        mpls[label] = tuple(sorted(
            nexthop(s, igp, ("PHP", None) if nbrs[s] == d else ("SWAP", label))
            for s in slots
        ))
    return unicast, mpls


def _slot(nh: tuple) -> tuple:
    """A next hop's (neighbour, interface)."""
    return nh[0], nh[2]


def _fold_unicast(tables: list[dict]) -> dict:
    out: dict = {}
    for table in tables:
        for key, (igp, prim, back) in table.items():
            cur = out.get(key)
            if cur is None or igp < cur[0]:
                out[key] = (igp, prim, back)
            elif igp == cur[0]:
                merged = tuple(sorted(set(cur[1]) | set(prim)))
                taken = {_slot(nh) for nh in merged}
                best: dict = {}
                for nh in (*cur[2], *back):
                    if _slot(nh) in taken:
                        continue
                    held = best.get(_slot(nh))
                    if held is None or nh[3] < held[3]:
                        best[_slot(nh)] = nh
                out[key] = (igp, merged, tuple(sorted(best.values())))
    return out


def _fold_mpls(tables: list[dict]) -> dict:
    out: dict = {}
    for table in tables:
        for key, nhs in table.items():
            cur = out.get(key)
            if cur is None or nhs[0][3] < cur[0][3]:
                out[key] = nhs
            elif nhs[0][3] == cur[0][3]:
                out[key] = tuple(sorted(set(cur) | set(nhs)))
    return out


def tables(
    g: topo.Graph, root: int, control: bool = False
) -> tuple[dict, dict]:
    """(unicast, mpls) tables of `root`, the unicast one with the backups
    under `backup_key`; `control=True`: see `CONTROL`."""
    order = sorted(
        g.meta["node_areas"][root], key=lambda a: g.meta["areas"][a])
    per_area = [_area_tables(g, a, root, control) for a in order]
    unicast: dict = {}
    for key, (_igp, prim, back) in _fold_unicast(
            [u for u, _m in per_area]).items():
        unicast[key] = prim
        if back:
            unicast[backup_key(key)] = back
    return unicast, _fold_mpls([m for _u, m in per_area])
