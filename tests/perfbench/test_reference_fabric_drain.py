"""The yardstick of `fabric_drain` (PR 38): its generator, its reference
against the program (`TpuSpfSolver.compute_routes`, through `compare.py`)
over drained sets that hold every case the no-transit rule has, and its
control. The driver's planted faults: test_perfbench_drain_faults.py."""

import dataclasses
import json

import numpy as np
import pytest
from perfbench_util import REPO

from perfbench import compare, reference, topo
from perfbench.drivers.decision_fib import program_dbs
from perfbench.references import fabric_drain
from perfbench.topologies import fat_tree_drained

CONFIGS = REPO / "perfbench" / "configs"


def topology_of(config: str) -> dict:
    return json.loads((CONFIGS / f"{config}.json").read_text())["topology"]


def fabric(k: int, drained) -> tuple[topo.Graph, int]:
    """fat_tree(k) with `drained` as its drained set, and the node under
    test of the configurations: the first ToR of pod 0."""
    g = topo.fat_tree(k)
    g.meta["drained"] = frozenset(drained)
    return g, topo.fat_tree_tor(g, 0, 0)


# ------------------------------------------------------------------ generator


def test_the_twin_is_the_plain_fat_tree_with_its_standing_set_on_meta():
    g = topo.build(topology_of("tiny_fabric_drain"))
    plain = topo.fat_tree(4)
    # topo.fat_tree(k), unchanged: a drain moves no edge and no metric
    assert (g.n, g.src.tolist(), g.dst.tolist(), g.metric.tolist()) == (
        plain.n, plain.src.tolist(), plain.dst.tolist(), plain.metric.tolist())
    assert {k: g.meta[k] for k in plain.meta} == plain.meta
    spines = {n for n in g.meta["drained"] if n < g.meta["n_core"]}
    aggs = g.meta["drained"] - spines
    assert len(spines) == 1 == len(aggs)
    (agg,) = aggs
    assert g.meta["n_core"] <= agg < g.meta["n_core"] + g.meta["n_agg"]
    assert fat_tree_drained.pod_of_agg(g, agg) != 0
    # the pool: every aggregation switch outside pod 0, less the standing one
    pool = g.meta["drain_pool"].tolist()
    assert pool == sorted(pool) and agg not in pool and len(pool) == 3 * 2 - 1
    assert all(fat_tree_drained.pod_of_agg(g, n) != 0 for n in pool)


def test_the_real_standing_set_is_what_the_configuration_states():
    g = topo.build(topology_of("fabric_drain"))
    assert g.n == 10125 and g.num_edges == 729000
    spines = sorted(n for n in g.meta["drained"] if n < g.meta["n_core"])
    aggs = sorted(g.meta["drained"] - set(spines))
    assert len(spines) == 8 == len(aggs)
    pods = [fat_tree_drained.pod_of_agg(g, n) for n in aggs]
    assert len(set(pods)) == 8 and 0 not in pods
    assert len(g.meta["drain_pool"]) == 4005 - 8
    assert not set(g.meta["drain_pool"].tolist()) & g.meta["drained"]


def test_one_standing_set_for_every_seed_and_another_for_another_graph_seed():
    spec = topology_of("fabric_drain")
    a, b = topo.build(spec), topo.build(spec)
    assert a.meta["drained"] == b.meta["drained"]
    assert topo.build({**spec, "graph_seed": 1}).meta["drained"] != a.meta["drained"]
    # --seed is the harness's; the generator is never handed it
    assert "seed" not in {k for k in spec if k != "graph_seed"}


@pytest.mark.parametrize("bad", [
    {"drained_aggs": 4}, {"drained_spines": 5}, {"drained_aggs": -1},
])
def test_the_generator_refuses_what_it_cannot_draw(bad):
    with pytest.raises(ValueError, match="fat_tree_drained"):
        topo.build({**topology_of("tiny_fabric_drain"), **bad})


# ------------------------------------------------ the program and the reference


def program_tables(g: topo.Graph, root: int) -> tuple[dict, dict]:
    """The program's tables for the graph with `meta["drained"]` drained:
    the databases as the driver builds them, straight into the LSDB, then
    one cold `compute_routes` on the device engine."""
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    adj_dbs, prefix_dbs = program_dbs(g)
    ls, ps = LinkState(), PrefixState()
    for i, db in enumerate(adj_dbs):
        ls.update_adjacency_db(
            dataclasses.replace(db, is_overloaded=i in g.meta["drained"]))
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    solver = TpuSpfSolver(native_rib="off")
    rdb = solver.compute_routes(ls, ps, topo.node_name(root))
    assert solver.spf_kernel_stats["engine_native"] == 0
    return (
        compare.plain_unicast(
            [e.to_unicast_route() for e in rdb.unicast_routes.values()]),
        compare.plain_mpls([e.to_mpls_route() for e in rdb.mpls_routes.values()]),
    )


def drained_sets(k: int) -> dict[str, list[int]]:
    """Named drained sets on fat_tree(k): the cases of the rule, then
    seeded random ones (any switch but the root, a fifth of them)."""
    g, root = fabric(k, ())
    half, n_core = g.meta["half"], g.meta["n_core"]
    cases = {
        "none": [],
        "a_neighbour_of_the_root": [topo.fat_tree_agg(g, 0, 0)],
        "every_agg_of_a_pod": [topo.fat_tree_agg(g, 1, a) for a in range(half)],
        "a_spine": [0],
        "one_agg_of_another_pod": [topo.fat_tree_agg(g, 2, half - 1)],
        "aggs_of_two_pods_and_a_spine": [
            topo.fat_tree_agg(g, 1, 0), topo.fat_tree_agg(g, 3, 1), n_core - 1],
        "a_tor_elsewhere": [topo.fat_tree_tor(g, 2, 0)],
        "the_root_itself": [root],
    }
    rng = np.random.default_rng(38)
    others = np.array([n for n in range(g.n) if n != root])
    for i in range(3):
        cases[f"random_{i}"] = sorted(
            rng.choice(others, size=g.n // 5, replace=False).tolist())
    return cases


CASES = [
    pytest.param(k, name, id=f"k{k}-{name}")
    for k in (4, 6) for name in drained_sets(k)
]


@pytest.mark.parametrize("k,name", CASES)
def test_the_program_equals_the_reference(k, name):
    g, root = fabric(k, drained_sets(k)[name])
    want_u, want_m = fabric_drain.tables(g, root)
    got_u, got_m = program_tables(g, root)
    assert compare.count_differences(got_u, want_u) == (0, [])
    assert compare.count_differences(got_m, want_m) == (0, [])
    assert want_u and want_m, "a table with nothing in it compares nothing"


def test_with_nothing_drained_it_is_the_plain_reference():
    for k in (4, 6):
        g, root = fabric(k, ())
        assert fabric_drain.tables(g, root) == reference.tables(g, root)


@pytest.mark.parametrize("k", [4, 6])
def test_what_the_rule_says_of_each_case(k):
    cases = drained_sets(k)
    g, root = fabric(k, ())
    plain_u, _plain_m = reference.tables(g, root)
    half = g.meta["half"]

    def unicast(name):
        gd, _ = fabric(k, cases[name])
        return fabric_drain.tables(gd, root)[0]

    # a drained neighbour of the root: a next hop toward itself only
    agg = cases["a_neighbour_of_the_root"][0]
    got = unicast("a_neighbour_of_the_root")
    via = {key for key, nhs in got.items()
           if topo.node_name(agg) in {nh[0] for nh in nhs}}
    assert via == {topo.loopback(agg)}
    assert set(got) == set(plain_u), "every switch stays reachable"
    # every aggregation switch of a pod drained: its ToRs have no route,
    # the drained switches themselves stay destinations
    got = unicast("every_agg_of_a_pod")
    tors = {topo.loopback(topo.fat_tree_tor(g, 1, t)) for t in range(half)}
    assert set(plain_u) - set(got) == tors
    assert all(topo.loopback(a) in got for a in cases["every_agg_of_a_pod"])
    # a drained spine: its plane has others, no route moves
    assert unicast("a_spine") == plain_u


# ----------------------------------------------------------------- the control


@pytest.mark.parametrize("k,name,aggs_elsewhere", [
    (4, "one_agg_of_another_pod", 1), (6, "one_agg_of_another_pod", 1),
    (4, "aggs_of_two_pods_and_a_spine", 2), (6, "aggs_of_two_pods_and_a_spine", 2),
    (4, "a_spine", 0), (6, "a_spine", 0),
])
def test_the_control_differs_by_the_tors_of_each_drained_aggs_pod(
        k, name, aggs_elsewhere):
    g, root = fabric(k, drained_sets(k)[name])
    want_u, want_m = fabric_drain.tables(g, root)
    ctl_u, ctl_m = fabric_drain.tables(g, root, control=True)
    tors_a_pod = k // 2
    assert compare.count_differences(ctl_u, want_u)[0] == aggs_elsewhere * tors_a_pod
    assert compare.count_differences(ctl_m, want_m)[0] == aggs_elsewhere * tors_a_pod
    assert "overload bit is ignored" in fabric_drain.CONTROL


def test_the_reference_imports_nothing_of_the_program():
    import ast

    for path in (REPO / "perfbench" / "references" / "fabric_drain.py",
                 REPO / "perfbench" / "topologies" / "fat_tree_drained.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                assert not any(a.name.startswith("openr_tpu") for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                assert not (node.module or "").startswith("openr_tpu")
