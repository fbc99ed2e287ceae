"""The fat-tree fabric with links standing down.

`topo.fat_tree(k)`, unchanged: the same arrays and the same `meta` keys, so
that `"root": {"tor": [0, 0]}` serves this graph as it serves `fabric10k`'s.
A failed link is withdrawn as Open/R withdraws one: Spark reports the
neighbour down at both ends and `LinkMonitor` re-advertises each end's
`AdjacencyDatabase` without the adjacency. The graph keeps every edge (a
link that comes back comes back with metric 1 and its interface names);
what this generator adds rides `meta`, drawn from `graph_seed`:

  failed      frozenset of links (u, v), u < v: the standing set, down
              from the first RIB to the last: `failed_tor_agg` ToR<->agg
              links and `failed_agg_spine` agg<->spine links, none of a
              switch of pod 0 (the pod of the node under test of the
              configurations on this generator), no switch with more than
              one. A driver that takes more links down hands on
              `as_published(g, set)`, the set as it stands in a `meta` of
              its own; `perfbench/references/fabric_linkfail.py` reads it
              from there.
  link_pools  {"tor_agg_up": int64 [P, 2]}: the links the traffic takes
              down, rows (agg, tor): every ToR<->agg link of the pods that
              are not pod 0 that touches no switch of the standing set.

Nothing here imports `openr_tpu`.
"""

from __future__ import annotations

import numpy as np

from perfbench import topo


def as_published(g: topo.Graph, failed) -> topo.Graph:
    """`g` with `failed` as its set of links down, for a table kept for
    the comparison: a copy whose `meta` holds its own copy of the set.
    `Graph.copy()` shares `meta`, and a shared set would compare every
    kept table with the last state."""
    kept = g.copy()
    kept.meta = {**g.meta, "failed": frozenset(failed)}
    return kept


def _draw(rng, candidates: np.ndarray, count: int, used: set) -> list:
    """`count` links of `candidates` (rows (u, v), u < v) in a seeded
    order, none of a switch in `used` or of one drawn before; `used`
    grows by their ends."""
    drawn = []
    for u, v in candidates[rng.permutation(len(candidates))].tolist():
        if len(drawn) == count:
            break
        if u in used or v in used:
            continue
        used.update((u, v))
        drawn.append((u, v))
    return drawn


def build(
    k: int, failed_tor_agg: int, failed_agg_spine: int, graph_seed: int
) -> topo.Graph:
    g = topo.fat_tree(k)
    half, n_core = g.meta["half"], g.meta["n_core"]
    pods = np.arange(1, k)
    # rows (agg, tor) and (spine, agg) of the pods but pod 0: u < v in both
    a = np.arange(half)
    tor_agg = np.array([
        (topo.fat_tree_agg(g, int(p), int(i)), topo.fat_tree_tor(g, int(p), int(j)))
        for p in pods for i in a for j in a
    ], dtype=np.int64).reshape(-1, 2)
    agg_spine = np.array([
        (int(i) * half + int(j), topo.fat_tree_agg(g, int(p), int(i)))
        for p in pods for i in a for j in a
    ], dtype=np.int64).reshape(-1, 2)
    rng = np.random.default_rng(graph_seed)
    used: set = set()
    failed = _draw(rng, tor_agg, failed_tor_agg, used)
    failed += _draw(rng, agg_spine, failed_agg_spine, used)
    if len(failed) != failed_tor_agg + failed_agg_spine:
        raise ValueError(
            f"fat_tree_failed: cannot draw {failed_tor_agg} ToR<->agg and "
            f"{failed_agg_spine} agg<->spine links off pod 0 with no switch "
            f"in two at k={k} ({n_core} spines)"
        )
    g.meta["failed"] = frozenset(failed)
    free = ~(np.isin(tor_agg[:, 0], list(used)) | np.isin(tor_agg[:, 1], list(used)))
    g.meta["link_pools"] = {"tor_agg_up": tor_agg[free]}
    return g
