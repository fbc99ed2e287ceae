"""The benchmark's own topology generators: plain arrays, no program types.

A `Graph` is what both sides are built from: a driver turns it into the
program's inputs (adjacency databases, a CSR view), `reference.py` turns it
into the expected routing tables. Nothing here imports `openr_tpu`, so a
later PR that changes the program's generators cannot move the yardstick.

Copies (by name) of `openr_tpu/utils/topogen.py`: `fat_tree` (layout and
edge order), `erdos_renyi_csr` (edge draw, without the padding), and the
naming conventions of `_mk_dbs` (`node-<i>`, `if_<u>_<v>`, node label
101+i, loopback 10.a.b.c/32).

A configuration file names its generator under "topology":
    {"generator": "fat_tree", "k": 90}
    {"generator": "erdos_renyi", "nodes": 100000, "avg_degree": 20,
     "max_metric": 64, "graph_seed": 0}
A new generator is a new function registered in `GENERATORS`.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def node_name(i: int) -> str:
    return f"node-{i}"


def if_name(u: int, v: int) -> str:
    return f"if_{u}_{v}"


def node_label(i: int) -> int:
    return 101 + i


def loopback(i: int) -> str:
    return f"10.{(i >> 16) & 0xFF}.{(i >> 8) & 0xFF}.{i & 0xFF}/32"


@dataclasses.dataclass
class Graph:
    """Directed edge list (both directions present), metrics mutable by
    `set_metric`. One loopback prefix and one node label per node."""

    n: int
    src: np.ndarray  # int64 [E]
    dst: np.ndarray  # int64 [E]
    metric: np.ndarray  # int64 [E]
    #: generator-specific ids the traffic draws links from
    meta: dict = dataclasses.field(default_factory=dict)
    _index: tuple | None = None

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])

    def edge_slot(self, u: int, v: int) -> int:
        """Where the directed edge u->v sits in the arrays."""
        if self._index is None:
            keys = self.src * self.n + self.dst
            order = np.argsort(keys, kind="stable")
            self._index = (keys[order], order)
        keys, order = self._index
        pos = int(np.searchsorted(keys, u * self.n + v))
        if pos >= len(keys) or keys[pos] != u * self.n + v:
            raise KeyError(f"no edge {u}->{v}")
        return int(order[pos])

    def set_metric(self, u: int, v: int, metric: int) -> None:
        """Both directions of the u<->v link."""
        self.metric[self.edge_slot(u, v)] = metric
        self.metric[self.edge_slot(v, u)] = metric

    def copy(self) -> "Graph":
        return Graph(
            self.n, self.src, self.dst, self.metric.copy(), self.meta,
            self._index,
        )


def fat_tree(k: int, metric: int = 1) -> Graph:
    """3-tier k-ary fat-tree: (k/2)^2 cores, k pods of k/2 agg + k/2 tor;
    every tor to every agg of its pod, agg i of each pod to cores
    [i*k/2, (i+1)*k/2)."""
    if k % 2 or k < 2:
        raise ValueError(f"fat_tree: k must be even and >= 2, got {k}")
    half = k // 2
    n_core, n_agg = half * half, k * half
    n = n_core + 2 * n_agg
    pods = np.arange(k)[:, None, None]
    a = np.arange(half)[None, :, None]
    j = np.arange(half)[None, None, :]
    shape = (k, half, half)
    agg = np.broadcast_to(n_core + pods * half + a, shape)
    tor = np.broadcast_to(n_core + n_agg + pods * half + j, shape)
    core = np.broadcast_to(a * half + j, shape)
    # per (pod, agg): half tor links then half core links, each both ways —
    # topogen.fat_tree's order, so the program's edge order is the same
    other = np.stack([tor, core], axis=2)  # [k, half, 2, half]
    u = np.broadcast_to(agg[:, :, None, :], other.shape)
    pairs = np.stack(
        [np.stack([u, other], -1), np.stack([other, u], -1)], axis=-2
    ).reshape(-1, 2)
    src, dst = pairs[:, 0].astype(np.int64), pairs[:, 1].astype(np.int64)
    return Graph(
        n, src, dst, np.full(src.shape[0], metric, np.int64),
        meta={"kind": "fat_tree", "k": k, "half": half,
              "n_core": n_core, "n_agg": n_agg},
    )


def fat_tree_agg(g: Graph, pod: int, i: int) -> int:
    return g.meta["n_core"] + pod * g.meta["half"] + i


def fat_tree_tor(g: Graph, pod: int, i: int) -> int:
    return g.meta["n_core"] + g.meta["n_agg"] + pod * g.meta["half"] + i


def erdos_renyi(
    nodes: int, avg_degree: int, max_metric: int, graph_seed: int
) -> Graph:
    """Backbone ring plus random chords, nodes*avg_degree/2 + nodes
    undirected links, metrics uniform in [1, max_metric], directed edges
    sorted by destination (stable)."""
    n = nodes
    rng = np.random.default_rng(graph_seed)
    target = n * avg_degree // 2
    ring_u = np.arange(n, dtype=np.int64)
    ring_v = (ring_u + 1) % n
    us = rng.integers(0, n, size=int(2.2 * target))
    vs = rng.integers(0, n, size=int(2.2 * target))
    keep = us != vs
    us, vs = us[keep], vs[keep]
    u_all = np.concatenate([ring_u, us])
    v_all = np.concatenate([ring_v, vs])
    lo, hi = np.minimum(u_all, v_all), np.maximum(u_all, v_all)
    _, first_idx = np.unique(lo * n + hi, return_index=True)
    first_idx = np.sort(first_idx)[: target + n]
    lo, hi = lo[first_idx], hi[first_idx]
    metric = rng.integers(1, max_metric + 1, size=lo.shape[0])
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    met = np.concatenate([metric, metric])
    order = np.argsort(dst, kind="stable")
    return Graph(
        n, src[order].astype(np.int64), dst[order].astype(np.int64),
        met[order].astype(np.int64),
        meta={"kind": "erdos_renyi", "links": int(lo.shape[0])},
    )


GENERATORS = {"fat_tree": fat_tree, "erdos_renyi": erdos_renyi}


def build(spec: dict) -> Graph:
    spec = dict(spec)
    name = spec.pop("generator")
    if name not in GENERATORS:
        raise ValueError(
            f"unknown topology generator {name!r} (have {sorted(GENERATORS)})"
        )
    return GENERATORS[name](**spec)
