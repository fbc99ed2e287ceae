"""The plain reference of `fabric_vips`: one ToR's tables when racks
announce anycast service VIPs, some of them with UCMP weights.

Written from the configuration's `guarantees` and docs/Decision.md; it
imports nothing of `openr_tpu` and takes nothing the program made. The
graph is a `perfbench/topologies/fat_tree_vips.py` graph: the VIPs, their
advertisers and the weights ride its `meta["vips"]`.

Loopbacks and node labels are `perfbench/reference.py`'s, by SP_ECMP:
scipy's Dijkstra from the root and each of its neighbours, once a table.
A VIP's route is read off those tables and nothing else: the distance to
an advertiser is the metric of its loopback's route, and the neighbours
on a shortest path to it are that route's next hops. Per VIP, all
advertisers carrying equal metric keys (the default metrics):

  chosen     the reachable advertisers at the least distance from the root
  next hops  every neighbour on a shortest path to any chosen advertiser
  metric     that distance
  weight     where any chosen advertiser states a weight: the sum of
             max(weight, 1) over the chosen advertisers the next hop
             serves, divided by the gcd of those sums over the route's
             next hops; else 0
  no route   where no advertiser is reachable, or the root advertises the
             VIP itself (it is at distance 0: the prefix is local)

`control=True` breaks one stated guarantee, UCMP: every next hop of a
weighted VIP carries weight 0, as plain ECMP would program it. Loopbacks,
labels and unweighted VIPs are as the reference's.
"""

from __future__ import annotations

import math

from perfbench import reference, topo

#: the guarantee `tables(..., control=True)` breaks, as control.py reports it
CONTROL = "ucmp broken (every next hop of a weighted VIP carries weight 0)"


def tables(
    g: topo.Graph, root: int, control: bool = False
) -> tuple[dict, dict]:
    """(unicast, mpls) tables of `root`."""
    unicast, mpls = reference.tables(g, root)
    vips = g.meta["vips"]
    indptr = vips["indptr"].tolist()
    adv, weight = vips["adv"].tolist(), vips["weight"].tolist()
    for v, prefix in enumerate(vips["prefix"]):
        slots = range(indptr[v], indptr[v + 1])
        if any(adv[s] == root for s in slots):
            continue
        # (distance, weight, the loopback route's next hops) an advertiser
        reached = [
            (via[0][3], weight[s], via) for s in slots
            if (via := unicast.get(topo.loopback(adv[s]))) is not None
        ]
        if not reached:
            continue
        least = min(d for d, _w, _via in reached)
        chosen = [(w, via) for d, w, via in reached if d == least]
        weighted = any(w > 0 for w, _via in chosen)
        # next hop (neighbour, address, interface) -> summed weight
        share: dict[tuple, int] = {}
        for w, via in chosen:
            for nh in via:
                share[nh[:3]] = share.get(nh[:3], 0) + max(w, 1)
        if weighted and not control:
            common = math.gcd(*share.values())
            share = {key: total // common for key, total in share.items()}
        else:
            share = dict.fromkeys(share, 0)
        unicast[prefix] = tuple(sorted(
            (*key, least, w, reference.AREA, None, None, ())
            for key, w in share.items()
        ))
    return unicast, mpls
