"""The fat-tree fabric with switches out for maintenance.

`topo.fat_tree(k)`, unchanged: the same arrays and the same `meta` keys, so
that `"root": {"tor": [0, 0]}` serves this graph as it serves `fabric10k`'s.
A drained switch has set Open/R's node-overload bit (`LinkMonitor`
`setNodeOverload`; `AdjacencyDatabase.is_overloaded`): it keeps every
adjacency and every metric, no other router sends transit traffic through
it, and it stays reachable as a destination. So no edge of the graph
changes; what this generator adds rides `meta`, drawn from `graph_seed`:

  drained     frozenset of node ids: the standing set, drained before the
              first RIB and for the whole run: `drained_aggs` aggregation
              switches, each in a pod of its own and none in pod 0 (the
              pod of the node under test of the configurations on this
              generator), and `drained_spines` spine switches. A driver
              that drains and undrains more hands on `as_published(g,
              set)`, the set as it stands in a `meta` of its own;
              `perfbench/references/fabric_drain.py` reads it from there.
  drain_pool  int64 [P], ascending: the switches the traffic draws from:
              every aggregation switch of the pods that are not pod 0,
              less the standing set's.

Nothing here imports `openr_tpu`.
"""

from __future__ import annotations

import numpy as np

from perfbench import topo


def pod_of_agg(g: topo.Graph, node: int) -> int:
    return (node - g.meta["n_core"]) // g.meta["half"]


def as_published(g: topo.Graph, drained) -> topo.Graph:
    """`g` with `drained` as its drained set, for a table kept for the
    comparison: a copy whose `meta` holds its own copy of the set.
    `Graph.copy()` shares `meta`, and a shared set would compare every
    kept table with the last state."""
    kept = g.copy()
    kept.meta = {**g.meta, "drained": frozenset(drained)}
    return kept


def build(
    k: int, drained_aggs: int, drained_spines: int, graph_seed: int
) -> topo.Graph:
    g = topo.fat_tree(k)
    half, n_core = g.meta["half"], g.meta["n_core"]
    if not (0 <= drained_aggs <= k - 1 and 0 <= drained_spines <= n_core):
        raise ValueError(
            f"fat_tree_drained: at most one drained aggregation switch in "
            f"each of the {k - 1} pods but pod 0 and at most {n_core} "
            f"spines, got {drained_aggs} and {drained_spines}"
        )
    rng = np.random.default_rng(graph_seed)
    pods = rng.choice(np.arange(1, k), size=drained_aggs, replace=False)
    aggs = [
        topo.fat_tree_agg(g, int(p), int(rng.integers(half))) for p in pods
    ]
    spines = rng.choice(n_core, size=drained_spines, replace=False)
    g.meta["drained"] = frozenset(aggs) | frozenset(map(int, spines))
    others = np.arange(
        topo.fat_tree_agg(g, 1, 0), n_core + g.meta["n_agg"], dtype=np.int64
    )
    g.meta["drain_pool"] = others[~np.isin(others, aggs)]
    return g
