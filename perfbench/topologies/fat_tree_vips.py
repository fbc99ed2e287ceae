"""The fat-tree fabric with anycast service VIPs, some of them weighted.

`topo.fat_tree(k)`, unchanged: the same arrays and the same `meta` keys, so
that `"root": {"tor": [0, 0]}` and the built-in link pool
`fat_tree_tor_agg` serve this graph as they serve `fabric10k`'s. What it
adds rides `meta["vips"]`, plain arrays drawn from `graph_seed`:

  * `vips` service VIPs, VIP v the prefix `10.200.<v >> 8>.<v & 255>/32`
    (disjoint from `topo.loopback`, which stays under `10.0.` up to
    65,535 switches);
  * VIP v is advertised by A ToRs (the racks that host replicas of the
    service), A drawn from `advertisers` with equal odds, the ToRs drawn
    without replacement from every ToR but the first of pod 0, the node
    under test of the configurations on this generator: it advertises no
    VIP;
  * VIPs 0 .. `weighted` - 1 carry a weight an advertiser, uniform in
    1..`max_weight` (the replicas that rack hosts); the others carry none
    (weight 0: anycast ECMP).

`meta["vips"]` in CSR form, VIP v's advertisers the slots
`indptr[v]:indptr[v + 1]`:

  prefix  [V]    str
  indptr  [V+1]  int64
  adv     [S]    int64  advertiser's node id, ascending within a VIP
  weight  [S]    int64  0 where the VIP is not weighted

Every switch keeps its loopback and node label (`topo.loopback`,
`topo.node_label`). Nothing here imports `openr_tpu`.
"""

from __future__ import annotations

import numpy as np

from perfbench import topo


def vip_prefix(v: int) -> str:
    return f"10.200.{v >> 8}.{v & 255}/32"


def build(
    k: int, vips: int, weighted: int, advertisers: list[int],
    max_weight: int, graph_seed: int,
) -> topo.Graph:
    g = topo.fat_tree(k)
    first_tor = topo.fat_tree_tor(g, 0, 0)
    tors = np.arange(first_tor + 1, g.n, dtype=np.int64)
    if not (0 <= weighted <= vips and 1 <= vips < 1 << 16):
        raise ValueError(
            f"fat_tree_vips: 1 <= vips < 65536 and 0 <= weighted <= vips, "
            f"got {weighted} of {vips}"
        )
    if not advertisers or min(advertisers) < 1 or max(advertisers) > len(tors):
        raise ValueError(
            f"fat_tree_vips: a VIP has 1..{len(tors)} advertisers (the ToRs "
            f"but the first), got {advertisers}"
        )
    rng = np.random.default_rng(graph_seed)
    counts = rng.choice(np.asarray(advertisers, np.int64), size=vips)
    adv = [np.sort(rng.choice(tors, size=int(a), replace=False)) for a in counts]
    indptr = np.concatenate(([0], np.cumsum(counts)))
    weight = rng.integers(1, max_weight + 1, size=int(indptr[-1]))
    weight[indptr[weighted]:] = 0
    g.meta["vips"] = {
        "prefix": [vip_prefix(v) for v in range(vips)],
        "indptr": indptr.astype(np.int64),
        "adv": np.concatenate(adv).astype(np.int64),
        "weight": weight.astype(np.int64),
    }
    return g
