"""LFA backups on the two-area backbone at CPU size (configuration
`backbone_lfa`): publication -> Decision(solver="tpu", enable_lfa) in two
areas -> the event's area solved cold with the LFA bits -> backups ->
cross-area fold -> Fib, over the benchmark's own generator, held to the
plain reference (`perfbench/references/backbone_lfa.py`, which imports
nothing of the program) with every unicast route's backups compared
exactly, and what the deployment added to the program:

  * `_fold_unicast`'s rule for backups at an equal-cost tie across areas
    (docs/Decision.md "LFA backups across areas");
  * the spans `spf:lfa_backups` (child of `spf:rib_unicast`) and
    `spf:lfa_dist_fetch` (its child), and the counters
    `decision.spf.lfa_routes`, `.lfa_nexthops`, `.lfa_unprotected`.
"""

import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from openr_tpu.decision.decision import merge_area_ribs
from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.monitor import profiling
from openr_tpu.ops.spf import INF_DIST
from openr_tpu.utils import topogen
from perfbench import compare, run, topo
from perfbench.drivers import decision_fib_lfa
from perfbench.drivers.decision_fib_areas import program_dbs
from perfbench.meter import Meter
from perfbench.references import backbone_lfa as reference
from perfbench.topologies import backbone_sites

sys.path.insert(0, str(Path(__file__).resolve().parent / "perfbench"))
from perfbench_util import REPO, load_benchmark, tiny_checkout  # noqa: E402

ROOT, ABR1 = 0, 1
PREFIXES = {"forwarding_algorithm": "SP_ECMP", "forwarding_type": "IP"}
CELL = "tiny_backbone_lfa.circuit_cost_out_lfa"
TWIN = {"areas": 2, "sites": 4, "routers": 4, "express": 2, "ring_metric": 10,
        "graph_seed": 0}


def graph(**over) -> topo.Graph:
    return backbone_sites.build(**{**TWIN, **over})


def backup_rows(unicast: dict) -> dict:
    return {k: v for k, v in unicast.items() if k.endswith(" backups")}


def program_tables(g: topo.Graph, overloaded=()) -> tuple[dict, dict]:
    """The program's tables of node 0, the unicast one with the backup
    rows: each area solved by `TpuSpfSolver(enable_lfa=True)` and folded
    by `merge_area_ribs`, as Decision does on a full round; the routers
    `overloaded` (node ids) set their overload bit."""
    adj_dbs, prefix_dbs = program_dbs(g, PREFIXES)
    names, areas = g.meta["names"], g.meta["areas"]
    per_area = {}
    solver = TpuSpfSolver(enable_lfa=True, native_rib="off")
    for a, area in enumerate(areas):
        ls, ps = LinkState(area), PrefixState(area)
        for (aa, node), db in adj_dbs.items():
            if aa == a:
                ls.update_adjacency_db(dataclasses.replace(
                    db, is_overloaded=node in overloaded))
        for (aa, _node), db in prefix_dbs.items():
            if aa == a:
                ps.update_prefix_db(db)
        per_area[area] = solver.compute_routes(ls, ps, names[ROOT])
    rib = merge_area_ribs(per_area, names[ROOT])
    unicast = compare.plain_unicast(
        [e.to_unicast_route() for e in rib.unicast_routes.values()]
        + decision_fib_lfa.backup_rows(rib))
    mpls = compare.plain_mpls(
        [e.to_mpls_route() for e in rib.mpls_routes.values()])
    return unicast, mpls


def driver_run(topology: dict, seconds: float = 0.6, seed: int = 2147483811):
    """`decision_fib_lfa.run` in this process on a configuration of the
    given topology, and the harness's comparison of what it kept."""
    config = run.load_json(REPO / "perfbench" / "configs" / "tiny_backbone_lfa.json")
    config["topology"] = {"generator": "backbone_sites", **topology}
    traffic = run.load_json(
        REPO / "perfbench" / "traffic" / "circuit_cost_out_lfa.json")
    window = run.Window(seconds, None)
    ctx = {"config": config, "traffic": traffic, "seed": seed,
           "window": window, "meter": Meter(), "say": lambda msg: None}
    obs = decision_fib_lfa.run(ctx)
    obs["events"] = window.events
    return obs, run.check_tables(obs, reference)


# ------------------------------------------------------ the program, at sizes


@pytest.mark.parametrize("topology", [
    {**TWIN},
    {**TWIN, "graph_seed": 1},
    {**TWIN, "sites": 6, "routers": 8, "express": 3, "graph_seed": 2},
    {**TWIN, "sites": 8, "routers": 4, "express": 4, "graph_seed": 1},
], ids=["twin", "twin-seed1", "6x8-seed2", "8x4-seed1"])
def test_the_driver_s_tables_equal_the_reference_with_every_backup(topology):
    obs, compared = driver_run(topology)
    assert obs["failed"] == 0 and obs["events"] > 0
    assert all(c["value"] == 0 for c in compared.values()), compared
    rows = [k for chk in obs["checks"]
            for k in backup_rows(compare.plain_unicast(chk["unicast"]))]
    assert rows, "backups were compared"
    # every event a cold solve of the circuit's area, the other cached
    c = obs["counters"]
    assert c["decision.spf.warm_fallbacks"] == c["decision.rebuild.full"] == obs[
        "events"]
    assert c["decision.rebuild.cached_areas"] == obs["events"]
    assert c.get("decision.spf.warm_starts", 0) == 0
    assert c.get("decision.spf.engine_native", 0) == 0


def test_the_full_size_graph_is_backbone_ksp_s_letter_for_letter():
    import json

    mine = json.loads((REPO / "perfbench/configs/backbone_lfa.json").read_text())
    ksp = json.loads((REPO / "perfbench/configs/backbone_ksp.json").read_text())
    assert mine["topology"] == ksp["topology"] and mine["root"] == ksp["root"]
    assert mine["decision"] == {**ksp["decision"], "enable_lfa": True}
    assert mine["prefixes"] == PREFIXES and mine["reduced"] == []


def test_a_graph_with_chords_in_one_area_has_high_coverage():
    """`topogen.wan_like`'s ring of core POPs with express chords and
    dual-homed stubs, as one area: the program equals the reference, and
    nearly every route has a backup (the backbone's rings protect about
    half: 1,068 of 2,045 loopbacks at full size)."""
    adj_dbs, _ = topogen.wan_like(60, seed=1)
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    src, dst, metric = [], [], []
    for db in adj_dbs:
        for a in db.adjacencies:
            src.append(index[db.this_node_name])
            dst.append(index[a.other_node_name])
            metric.append(a.metric)
    n = len(adj_dbs)
    g = topo.Graph(
        n, np.array(src, np.int64), np.array(dst, np.int64),
        np.array(metric, np.int64),
        meta={"areas": ["0"], "edge_area": np.zeros(len(src), np.int64),
              "names": [f"w{i}" for i in range(n)],
              "node_areas": [[0]] * n},
    )
    got = program_tables(g)
    want = reference.tables(g, ROOT)
    assert got == want
    routes = len(want[1])
    assert routes == n - 1
    assert len(backup_rows(want[0])) >= 0.9 * routes


def test_an_overloaded_neighbour_backs_up_only_itself():
    g = graph()
    nbrs = sorted(set(g.dst[g.src == ROOT].tolist()))
    # a ring neighbour of the root in area "1": overloaded, no transit
    over = next(n for n in nbrs if g.meta["names"][n].startswith("a1-s0-"))
    g.meta = {**g.meta, "overloaded": [over]}
    got = program_tables(g, overloaded={over})
    want = reference.tables(g, ROOT)
    assert got == want
    name = g.meta["names"][over]
    for key, nhs in backup_rows(want[0]).items():
        if any(nh[0] == name for nh in nhs):
            assert key == reference.backup_key(topo.loopback(over)), key
    plain = reference.tables(graph(), ROOT)[0]
    assert any(nh[0] == name for nhs in backup_rows(plain).values() for nh in nhs)


def test_abr1_s_loopback_at_the_tie_folds_by_the_stated_rule():
    """Both areas reach `abr-1` at the same cost: the primaries are the
    union, the backups the union of both areas' less the merged
    primaries, each at its own metric."""
    g = graph()
    per_area = [reference._area_tables(g, a, ROOT, False)[0] for a in (0, 1)]
    igp, prims, backs = zip(*(t[topo.loopback(ABR1)] for t in per_area))
    assert igp[0] == igp[1] and all(backs), "a tie, with backups in both areas"
    got, _ = program_tables(g)
    key = topo.loopback(ABR1)
    assert got[key] == tuple(sorted(prims[0] + prims[1]))
    merged = got[reference.backup_key(key)]
    assert merged == tuple(sorted(backs[0] + backs[1]))
    assert {nh[5] for nh in merged} == {"1", "2"}


def test_the_program_s_fold_keeps_the_winner_s_backups_off_the_tie():
    """Area "1" costed out toward `abr-1` (its ring links): area "2" is
    nearer and its route wins whole, backups included."""
    g = graph()
    names = g.meta["names"]
    for r in ("a1-s0-r1", "a1-s0-r3"):
        g.set_metric(names.index(r), ABR1, 10000)
    got, _ = program_tables(g)
    want, _ = reference.tables(g, ROOT)
    key = topo.loopback(ABR1)
    assert got[key] == want[key] and {nh[5] for nh in got[key]} == {"2"}
    back = got.get(reference.backup_key(key), ())
    assert back == want.get(reference.backup_key(key), ())
    assert {nh[5] for nh in back} <= {"2"}


# ------------------------------------------------- the control and the faults


def test_the_control_breaks_the_strict_inequality_and_nothing_else():
    g = graph()
    want_u, want_m = reference.tables(g, ROOT)
    ctl_u, ctl_m = reference.tables(g, ROOT, control=True)
    assert ctl_m == want_m
    n, _ = compare.count_differences(ctl_u, want_u)
    assert n > 0
    primaries = {k: v for k, v in want_u.items() if not k.endswith(" backups")}
    assert {k: v for k, v in ctl_u.items() if k in primaries} == primaries
    assert "strict inequality" in reference.CONTROL


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("backbone_lfa"))


def drop_the_backups(monkeypatch):
    """The program builds no backup."""
    monkeypatch.setattr(TpuSpfSolver, "_lfa_backups", lambda self, *a: None)


def make_the_inequality_non_strict(monkeypatch):
    """A neighbour whose path ties back through the root counts as
    loop-free: the kernel's LFA bits recomputed with <=."""
    real = TpuSpfSolver._lfa_backups

    def loose(self, csr, my_id, nbr_ids, fh, lfa, dist, *rest):
        mat = np.asarray(dist).astype(np.int64)
        n = len(nbr_ids)
        d_root, d_nbr = mat[:, 0], mat[:, 1:1 + n]
        to_root = mat[my_id, 1:1 + n]
        reach = ((d_root < INF_DIST)[:, None] & (d_nbr < INF_DIST)
                 & (to_root < INF_DIST)[None, :])
        bits = lfa.copy()
        bits[:n] = (reach & (d_nbr <= to_root[None, :] + d_root[:, None])).T
        return real(self, csr, my_id, nbr_ids, fh, bits, dist, *rest)

    monkeypatch.setattr(TpuSpfSolver, "_lfa_backups", loose)


def keep_the_first_area_s_backups_at_a_tie(monkeypatch):
    """The fold before this deployment: at a tie the first area's backups
    stay as they are."""
    from openr_tpu.decision import decision

    monkeypatch.setattr(decision, "_union_backups", lambda a, b, p: a)


@pytest.mark.parametrize("fault", [
    drop_the_backups, make_the_inequality_non_strict,
    keep_the_first_area_s_backups_at_a_tie,
])
def test_a_planted_fault_reads_not_correct(checkout, monkeypatch, fault):
    fault(monkeypatch)
    rc, line = run.run_cell(checkout, CELL, 11, 1.0, False, time.perf_counter())
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["unicast_routes_differ"]["value"] > 0
    assert line["compared"]["mpls_routes_differ"]["value"] == 0


def test_a_sound_run_puts_the_program_s_makers_back(checkout):
    import openr_tpu.decision.decision as decision_module
    import openr_tpu.fib as fib_module

    before = decision_module.Decision, fib_module.MockFibHandler
    rc, line = run.run_cell(checkout, CELL, 12, 1.0, False, time.perf_counter())
    assert rc == 0 and line["correct"] is True
    assert (decision_module.Decision, fib_module.MockFibHandler) == before


# ------------------------------------------------------ spans and counters


def test_the_backups_pass_is_one_span_a_rebuild_inside_rib_unicast():
    g = graph()
    adj_dbs, prefix_dbs = program_dbs(g, PREFIXES)
    ls, ps = LinkState("1"), PrefixState("1")
    for (a, _n), db in adj_dbs.items():
        if a == 0:
            ls.update_adjacency_db(db)
    for (a, _n), db in prefix_dbs.items():
        if a == 0:
            ps.update_prefix_db(db)
    solver = TpuSpfSolver(enable_lfa=True, native_rib="off")
    with profiling.collect() as rec:
        rib = solver.compute_routes(ls, ps, g.meta["names"][ROOT])
    parents = {name: parent for name, parent, _s, _e in rec.spans}
    assert [n for n, *_ in rec.spans].count("spf:lfa_backups") == 1
    assert parents["spf:lfa_dist_fetch"] == "spf:lfa_backups"
    assert parents["spf:lfa_backups"] == "spf:rib_unicast"
    st = solver.spf_kernel_stats
    routes = len(rib.unicast_routes)
    with_backups = [e for e in rib.unicast_routes.values() if e.backup_nexthops]
    assert st["lfa_routes"] == len(with_backups) > 0
    assert st["lfa_nexthops"] == sum(len(e.backup_nexthops) for e in with_backups)
    assert st["lfa_routes"] + st["lfa_unprotected"] == routes
    # without LFA: no pass, no span, nothing counted
    plain = TpuSpfSolver(native_rib="off")
    with profiling.collect() as rec:
        plain.compute_routes(ls, ps, g.meta["names"][ROOT])
    assert "spf:lfa_backups" not in {n for n, *_ in rec.spans}
    assert plain.spf_kernel_stats["lfa_routes"] == 0


def test_a_traced_twin_run_reads_the_spans_and_counters(checkout):
    from perfbench_util import last_line, run_py

    line = last_line(run_py(checkout, "--workload", CELL, "--seed",
                            "2147483901", "--seconds", "3", "--trace", "1"))
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < got["lfa_dist_fetch_ms"] < got["lfa_backups_ms"] < got[
        "compute_rib_ms"]
    assert got["lfa_routes_per_event"] > 0
    assert got["lfa_nexthops_per_event"] >= got["lfa_routes_per_event"]
    assert got["warm_fallbacks_per_event"] == 1.0
    assert got["area_solves_per_event"] == got["cached_areas_per_event"] == 1.0


def test_the_reference_imports_nothing_of_the_program():
    text = Path(reference.__file__).read_text()
    assert "openr_tpu" not in text.split('"""', 2)[2]
    cells = {w["name"]: w for w in load_benchmark()["workloads"]}
    assert cells["backbone_lfa.circuit_cost_out"]["config"] == "backbone_lfa"
