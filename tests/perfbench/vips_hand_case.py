"""A k = 4 fabric with seven hand-placed VIPs, and what the first ToR's
tables hold for them, worked out on paper: shared by the reference's
test (tests/perfbench/test_perfbench_vips.py) and the program's
(tests/test_fabric_vips_normal_path.py).

fat_tree(4): cores 0-3; aggs 4 + 2p + i; ToRs 12 + 2p + j. The root is
ToR 12 (pod 0); its neighbours are aggs 4 (plane 0) and 5 (plane 1). ToR
13 shares its pod (distance 2, both planes); every other ToR is at
distance 4, through both planes while all metrics are 1. THE LINK, agg 7
<-> ToR 14 (pod 1, plane 1), raised to 10, takes plane 1 out of ToR 14
and out of nothing else.

  VIP  advertisers (weight)   held by the root
  0    13, 14                 anycast; 13 is nearer: both planes, metric 2
  1    14 (2), 16 (4)         weighted: 6 a plane = 1 : 1; with THE LINK
                              raised plane 0 serves both (6), plane 1
                              only 16 (4) = 3 : 2 (common factor 2)
  2    13 (3), 15 (5)         weighted; 13 is nearer: 3 a plane = 1 : 1
  3    14 (4)                 weighted, one advertiser: 1 : 1; raised:
                              plane 0 alone, 4 / 4 = 1
  4    12, 18                 the root advertises it: no route
  5    14, 16 (3)             partly weighted: 14 counts 1: 4 a plane =
                              1 : 1; raised: 4 : 3
  6    14, 17                 anycast: both planes, metric 4, raised or not
"""

from __future__ import annotations

import numpy as np

from perfbench import topo
from perfbench.topologies.fat_tree_vips import vip_prefix

ROOT = 12
PLANES = (4, 5)
THE_LINK = (7, 14)
RAISED = 10

#: (advertiser, weight) a VIP
VIPS = [
    [(13, 0), (14, 0)],
    [(14, 2), (16, 4)],
    [(13, 3), (15, 5)],
    [(14, 4)],
    [(12, 0), (18, 0)],
    [(14, 0), (16, 3)],
    [(14, 0), (17, 0)],
]

#: VIP -> (metric, weight on plane 0, weight on plane 1); None: no next
#: hop on that plane; a VIP that is absent has no route
ALL_AT_1 = {
    0: (2, 0, 0), 1: (4, 1, 1), 2: (2, 1, 1), 3: (4, 1, 1), 5: (4, 1, 1),
    6: (4, 0, 0),
}
LINK_RAISED = {**ALL_AT_1, 1: (4, 3, 2), 3: (4, 1, None), 5: (4, 4, 3)}
#: with THE LINK raised and ToR 16's weight for VIP 1 changed from 4 to 6
LINK_RAISED_VIP1_AT_6 = {**LINK_RAISED, 1: (4, 4, 3)}

#: what `_mk_nexthops` walks for the four VIPs that state a weight (1, 2,
#: 3, 5): (chosen advertiser, first hop) pairs
SLOT_VISITS_ALL_AT_1 = 4 + 2 + 2 + 4
SLOT_VISITS_LINK_RAISED = 3 + 2 + 1 + 3


def graph() -> topo.Graph:
    g = topo.fat_tree(4)
    counts = [len(v) for v in VIPS]
    g.meta["vips"] = {
        "prefix": [vip_prefix(v) for v in range(len(VIPS))],
        "indptr": np.concatenate(([0], np.cumsum(counts))).astype(np.int64),
        "adv": np.array([a for v in VIPS for a, _w in v], np.int64),
        "weight": np.array([w for v in VIPS for _a, w in v], np.int64),
    }
    return g


def set_weight(g: topo.Graph, vip: int, advertiser: int, weight: int) -> None:
    """A rack changes the weight it advertises a VIP with. The arrays are
    replaced, not written: `Graph.copy` shares `meta`."""
    vips = g.meta["vips"]
    lo, hi = vips["indptr"][vip], vips["indptr"][vip + 1]
    slot = lo + int(np.flatnonzero(vips["adv"][lo:hi] == advertiser)[0])
    new = vips["weight"].copy()
    new[slot] = weight
    g.meta = {**g.meta, "vips": {**vips, "weight": new}}


def vip_routes(expected: dict) -> dict:
    """`expected` (one of the three tables above) in the reference's
    plain form: {prefix: sorted next hops}."""
    out = {}
    for v, (metric, *weights) in expected.items():
        out[vip_prefix(v)] = tuple(sorted(
            (topo.node_name(n), topo.node_name(n), topo.if_name(ROOT, n),
             metric, w, "0", None, None, ())
            for n, w in zip(PLANES, weights) if w is not None
        ))
    return out
