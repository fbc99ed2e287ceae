"""A wide-area backbone in areas: a ring of site rings an area, two border
routers that sit in every area.

The shape of `benchmarks/bench_ksp_lfa.py` `build_backbone` (a ring of
site rings, two circuits between adjacent sites), copied as plain arrays
so that a change to that script cannot move the benchmark, and split into
areas as an Open/R `AreaConfig` deployment is:

  * an area is a ring of `sites` sites; a site is a ring of `routers`
    routers at metric `ring_metric`;
  * adjacent sites are joined by two circuits, router 0 <-> router 0 and
    router `routers // 2` <-> router `routers // 2`, metric 100 + a value
    in 0..99 drawn from `graph_seed` (a circuit's two directions equal);
  * `express` express circuits an area between pairs of sites at ring
    distance `sites // 4` .. `sites // 2` drawn from `graph_seed`, router
    `routers // 4` <-> router `routers // 4`, metric 60 x the ring distance;
  * routers 0 and `routers // 2` of site 0 are the same two routers in
    every area: `abr-0` (node 0) and `abr-1` (node 1).

Nodes: 0 and 1 the border routers, then area by area, site by site, the
routers that are left. Every directed edge lies in one area:
`meta["edge_area"]` (an index into `meta["areas"]`, an edge). The
deployment's names ride `meta["names"]`; `meta["own_metric"]` is every
edge's configured metric, which a restore goes back to. One loopback
(`topo.loopback`) and one node label (`topo.node_label`) a router,
advertised in each area the router sits in: `meta["node_areas"]`.
`meta["link_pools"]["circuits_off_root"]`: every inter-site and express
circuit of every area that does not end at node 0.
"""

from __future__ import annotations

import numpy as np

from perfbench import topo


def build(
    areas: int, sites: int, routers: int, express: int, ring_metric: int,
    graph_seed: int,
) -> topo.Graph:
    if sites < 4 or routers < 4 or routers % 4:
        raise ValueError(
            f"backbone_sites: sites >= 4 and routers a multiple of 4, got "
            f"{sites} sites of {routers}"
        )
    rng = np.random.default_rng(graph_seed)
    half, quarter = routers // 2, routers // 4
    area_names = [str(a + 1) for a in range(areas)]
    names = ["abr-0", "abr-1"]
    node_areas: list[list[int]] = [list(range(areas)), list(range(areas))]
    width = len(str(max(sites, routers) - 1))
    us: list[int] = []
    vs: list[int] = []
    ms: list[int] = []
    edge_area: list[int] = []
    circuits: list[tuple[int, int]] = []
    for a, area in enumerate(area_names):
        node = np.empty((sites, routers), np.int64)
        for s in range(sites):
            for r in range(routers):
                if s == 0 and r in (0, half):
                    node[s, r] = 0 if r == 0 else 1
                    continue
                node[s, r] = len(names)
                names.append(f"a{area}-s{s:0{width}d}-r{r:0{width}d}")
                node_areas.append([a])

        def link(u: int, v: int, metric: int) -> None:
            us.append(u), vs.append(v), ms.append(metric), edge_area.append(a)

        for s in range(sites):
            for r in range(routers):
                link(node[s, r], node[s, (r + 1) % routers], ring_metric)
        for s in range(sites):
            nxt = (s + 1) % sites
            for r in (0, half):
                link(node[s, r], node[nxt, r], 100 + int(rng.integers(0, 100)))
                circuits.append((int(node[s, r]), int(node[nxt, r])))
        drawn: set[tuple[int, int]] = set()
        while len(drawn) < express:
            s = int(rng.integers(0, sites))
            d = int(rng.integers(sites // 4, sites // 2 + 1))
            pair = (min(s, (s + d) % sites), max(s, (s + d) % sites))
            if pair in drawn:
                continue
            drawn.add(pair)
            link(node[pair[0], quarter], node[pair[1], quarter], 60 * d)
            circuits.append(
                (int(node[pair[0], quarter]), int(node[pair[1], quarter])))
    u, v = np.array(us, np.int64), np.array(vs, np.int64)
    m = np.array(ms, np.int64)
    e_area = np.array(edge_area, np.int64)
    metric = np.concatenate([m, m])
    return topo.Graph(
        len(names), np.concatenate([u, v]), np.concatenate([v, u]), metric,
        meta={
            "kind": "backbone_sites",
            "areas": area_names,
            "edge_area": np.concatenate([e_area, e_area]),
            "names": names,
            "node_areas": node_areas,
            "own_metric": metric.copy(),
            "link_pools": {"circuits_off_root": np.array(
                [c for c in circuits if 0 not in c], np.int64)},
        },
    )
