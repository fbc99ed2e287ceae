"""The yardstick of `fabric_vips` (PR 34): its generator, its reference on
a case worked out by hand and against the program's scalar oracle, and
its control. The driver's planted faults: test_perfbench_vips_faults.py."""

import json
from pathlib import Path

import numpy as np
import pytest
import vips_hand_case as hand
from perfbench_util import REPO, run_py, tiny_checkout

from perfbench import compare, reference, topo
from perfbench.references import fabric_vips
from perfbench.topologies import fat_tree_vips

CONFIGS = REPO / "perfbench" / "configs"


def topology_of(config: str) -> dict:
    return json.loads((CONFIGS / f"{config}.json").read_text())["topology"]


def twin_graph() -> topo.Graph:
    return topo.build(topology_of("tiny_fabric_vips"))


# ------------------------------------------------------------------ generator


def test_the_twin_has_the_counts_its_file_states():
    g = twin_graph()
    plain = topo.fat_tree(4)
    # topo.fat_tree(k), unchanged: the flap cell's pools and root serve it
    assert (g.n, g.src.tolist(), g.dst.tolist(), g.metric.tolist()) == (
        plain.n, plain.src.tolist(), plain.dst.tolist(), plain.metric.tolist())
    assert {k: g.meta[k] for k in plain.meta} == plain.meta
    vips = g.meta["vips"]
    counts = np.diff(vips["indptr"])
    assert len(vips["prefix"]) == 16 == len(counts)
    assert set(counts.tolist()) <= {2, 3, 4} and counts.sum() == len(vips["adv"])
    weighted = np.repeat(np.arange(16) < 8, counts)
    assert ((vips["weight"] >= 1) & (vips["weight"] <= 8))[weighted].all()
    assert (vips["weight"][~weighted] == 0).all()
    for lo, hi in zip(vips["indptr"][:-1], vips["indptr"][1:]):
        adv = vips["adv"][lo:hi]
        assert (np.diff(adv) > 0).all(), "drawn without replacement, ascending"


def test_the_root_advertises_no_vip_and_every_advertiser_is_a_tor():
    g = twin_graph()
    root = topo.fat_tree_tor(g, 0, 0)
    adv = g.meta["vips"]["adv"]
    assert root not in adv and adv.min() > root and adv.max() < g.n


def test_vips_and_loopbacks_are_disjoint_at_the_real_size_too():
    real = topology_of("fabric_vips")
    switches = 5 * real["k"] ** 2 // 4
    loopbacks = {topo.loopback(i) for i in range(switches)}
    vips = [fat_tree_vips.vip_prefix(v) for v in range(real["vips"])]
    assert len(set(vips)) == real["vips"] == 2048 and not loopbacks & set(vips)
    assert real["weighted"] == 1024 and switches == 10125


def test_one_graph_for_every_seed_and_another_for_another_graph_seed():
    spec = topology_of("tiny_fabric_vips")
    a, b = topo.build(spec), topo.build(spec)
    for key in ("indptr", "adv", "weight"):
        assert a.meta["vips"][key].tolist() == b.meta["vips"][key].tolist()
    other = topo.build({**spec, "graph_seed": 1})
    assert other.meta["vips"]["adv"].tolist() != a.meta["vips"]["adv"].tolist()
    # --seed is the harness's; the generator is never handed it
    assert "seed" not in {k for k in spec if k != "graph_seed"}


@pytest.mark.parametrize("bad", [
    {"vips": 0}, {"weighted": 17}, {"advertisers": []}, {"advertisers": [8]},
    {"advertisers": [0, 2]},
])
def test_the_generator_refuses_what_it_cannot_draw(bad):
    with pytest.raises(ValueError, match="fat_tree_vips"):
        topo.build({**topology_of("tiny_fabric_vips"), **bad})


# ------------------------------------------------------------------ reference


STEPS = {
    "all_links_at_1": (False, None, hand.ALL_AT_1),
    "the_link_raised": (True, None, hand.LINK_RAISED),
    "the_link_raised_and_a_weight_changed": (True, 6, hand.LINK_RAISED_VIP1_AT_6),
}


def hand_graph(raised: bool, vip1_weight) -> topo.Graph:
    g = hand.graph()
    if raised:
        g.set_metric(*hand.THE_LINK, hand.RAISED)
    if vip1_weight is not None:
        hand.set_weight(g, 1, 16, vip1_weight)
    return g


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_reference_on_the_case_worked_out_by_hand(step):
    raised, vip1_weight, expected = STEPS[step]
    g = hand_graph(raised, vip1_weight)
    unicast, mpls = fabric_vips.tables(g, hand.ROOT)
    plain_u, plain_m = reference.tables(g, hand.ROOT)
    vip_keys = set(g.meta["vips"]["prefix"])
    assert {k: v for k, v in unicast.items() if k in vip_keys} == hand.vip_routes(
        expected)
    # VIP 4, which the root advertises itself, has no route
    assert fat_tree_vips.vip_prefix(4) not in unicast
    # loopbacks and labels are the plain reference's, untouched
    assert {k: v for k, v in unicast.items() if k not in vip_keys} == plain_u
    assert mpls == plain_m


def program_tables(g: topo.Graph, root: int):
    """The program's scalar oracle on the same graph, in plain form: a
    second witness for the reference, not the reference."""
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.oracle import compute_routes

    from perfbench.drivers.decision_fib_vips import program_dbs

    adj_dbs, prefix_dbs = program_dbs(g)
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    rdb = compute_routes(ls, ps, topo.node_name(root), vectorize=False)
    return (
        compare.plain_unicast(e.to_unicast_route() for e in rdb.unicast_routes.values()),
        compare.plain_mpls(e.to_mpls_route() for e in rdb.mpls_routes.values()),
    )


def flapped_twin() -> topo.Graph:
    g = twin_graph()
    g.set_metric(topo.fat_tree_agg(g, 2, 1), topo.fat_tree_tor(g, 2, 0), 10)
    g.set_metric(topo.fat_tree_agg(g, 3, 0), topo.fat_tree_tor(g, 3, 1), 10)
    return g


@pytest.mark.parametrize("name,g", [
    ("twin", twin_graph()), ("twin_flapped", flapped_twin()),
    ("hand", hand_graph(False, None)), ("hand_raised", hand_graph(True, 6)),
], ids=lambda x: x if isinstance(x, str) else "")
def test_the_reference_agrees_with_the_programs_scalar_oracle(name, g):
    root = topo.fat_tree_tor(g, 0, 0)
    want_u, want_m = fabric_vips.tables(g, root)
    got_u, got_m = program_tables(g, root)
    assert compare.count_differences(got_u, want_u) == (0, [])
    assert compare.count_differences(got_m, want_m) == (0, [])
    assert len(want_m) == g.n - 1 < len(want_u)


# -------------------------------------------------------------------- control


@pytest.mark.parametrize("g", [twin_graph(), flapped_twin()], ids=["twin", "flapped"])
def test_the_control_breaks_ucmp_and_nothing_else(g):
    root = topo.fat_tree_tor(g, 0, 0)
    want_u, want_m = fabric_vips.tables(g, root)
    ctl_u, ctl_m = fabric_vips.tables(g, root, control=True)
    assert ctl_m == want_m and set(ctl_u) == set(want_u)
    differ = {k for k in want_u if ctl_u[k] != want_u[k]}
    weighted = set(g.meta["vips"]["prefix"][:8])
    assert differ == weighted & set(want_u) and len(differ) == 8
    assert all(nh[4] == 0 for k in differ for nh in ctl_u[k])
    assert "ucmp" in fabric_vips.CONTROL


def test_control_py_reads_not_correct_on_the_twin(tmp_path):
    root = tiny_checkout(tmp_path)
    proc = run_py(root, "--workload", "tiny_fabric_vips.tor_uplink_flap",
                  "--seeds", "5+4000000007", "--events", "9", script="control.py")
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    assert len(rows) == 2
    for row in rows:
        assert row["correct"] is False and row["control"] == fabric_vips.CONTROL
        assert row["unicast_routes_differ"]["value"] == 8
        assert row["mpls_routes_differ"]["value"] == 0


def test_the_reference_and_the_generator_import_nothing_of_the_program():
    for module in (fabric_vips, fat_tree_vips):
        text = Path(module.__file__).read_text()
        assert "openr_tpu" not in text.split('"""', 2)[2], module.__name__
