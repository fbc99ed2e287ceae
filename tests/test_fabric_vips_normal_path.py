"""Anycast VIPs with UCMP weights through the normal path at CPU size
(configuration `fabric_vips`, PR 34): publication -> Decision(solver="tpu")
-> cold solve / warm start / prefix-only reassembly -> the general scalar
election -> Fib -> handler, on a k = 4 fabric with seven hand-placed VIPs
(tests/perfbench/vips_hand_case.py), held to the plain reference
(`perfbench/references/fabric_vips.py`, which imports nothing of the
program), to the tables worked out by hand, and to the scalar oracle byte
for byte. What the deployment added to the program:

  * the span `spf:unicast_general` around `_unicast_general`, in the cold
    path (inside `spf:rib_unicast`), the warm path (inside
    `spf:warm_reassemble`) and the prefix-only path;
  * the counters `decision.spf.general_prefixes`, `.ucmp_prefixes`,
    `.ucmp_slot_visits` and `.multi_scoped`; since PR 37 `.complex_scoped`:
    a warm start re-elects the weighted VIPs that a changed node
    advertises, and no longer all of them.

One story (module fixture): the first RIB; THE LINK raised (a warm start);
ToR 16 changes the weight it advertises VIP 1 with (prefix-only); THE LINK
restored (a warm start).
"""

import asyncio
import dataclasses
import json
import sys
import time
from pathlib import Path

import pytest

from openr_tpu.common import constants as C
from openr_tpu.config import Config
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.fib import Fib, MockFibHandler
from openr_tpu.fib.fib import CLIENT_ID_OPENR
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters, names, perf, profiling
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.network import IpPrefix
from openr_tpu.types.serde import to_wire
from perfbench import compare, topo
from perfbench.drivers.decision_fib import AREA
from perfbench.drivers.decision_fib_vips import program_dbs
from perfbench.references import fabric_vips as reference

sys.path.insert(0, str(Path(__file__).resolve().parent / "perfbench"))
import vips_hand_case as hand  # noqa: E402

COUNTED = ("general_prefixes", "ucmp_prefixes", "ucmp_slot_visits", "multi_scoped",
           "complex_scoped")
#: VIP 2 (advertisers 13 and 15): weighted, and untouched by THE LINK
VIP_2 = IpPrefix.make(hand.vip_prefix(2))
REBUILDS = ("decision.rebuild.full", "decision.rebuild.topo_delta",
            "decision.rebuild.prefix_only", "decision.spf.warm_starts")


def lsdb_of(g: topo.Graph):
    """The graph's databases as the driver builds them, and the LSDB they
    make, applied directly."""
    from openr_tpu.decision.linkstate import LinkState, PrefixState

    adj_dbs, prefix_dbs = program_dbs(g)
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    return adj_dbs, ls, ps


async def run_story() -> dict:
    g = hand.graph()
    adj_dbs, prefix_dbs = program_dbs(g)
    me = topo.node_name(hand.ROOT)
    cfg = Config.default(me)
    cfg.node.decision.native_rib = "off"
    counters = Counters()
    pubs = ReplicateQueue(name="pubs")
    routes = ReplicateQueue(name="routes")
    perf_events = ReplicateQueue(name="perf_events")
    synced = asyncio.Event()
    dec = Decision(cfg, pubs.get_reader(), routes, solver="tpu",
                   counters=counters, initial_sync_event=synced)
    handler = MockFibHandler()
    fib = Fib(cfg, routes.get_reader(), handler,
              perf_events_queue=perf_events, counters=counters)
    traces = perf_events.get_reader("test")
    versions: dict[str, int] = {}

    def value(key: str, db) -> Value:
        versions[key] = versions.get(key, 0) + 1
        return Value(version=versions[key], originator_id=db.this_node_name,
                     value=to_wire(db)).with_hash()

    async def until(pred, what):
        deadline = time.monotonic() + 120
        while not pred():
            assert dec.last_rebuild_error is None, dec.last_rebuild_error
            assert time.monotonic() < deadline, f"timed out waiting for {what}"
            await asyncio.sleep(0.002)

    def stats() -> dict:
        return {k: dec._tpu.spf_kernel_stats[k] for k in COUNTED}

    async def step(label: str, before: dict, rebuilds0: dict) -> dict:
        """What the rebuild that just reached Fib did and left."""
        after = stats()
        ls, = dec.link_states.values()
        ps, = dec.prefix_states.values()
        oracle = oracle_compute_routes(ls, ps, me, vectorize=False)
        return {
            "label": label,
            "grew": {k: after[k] - before[k] for k in COUNTED},
            "exported": {k: counters.get(f"decision.spf.{k}") for k in COUNTED},
            "held": after,
            "rebuilds": {k: counters.get(k) - rebuilds0[k] for k in REBUILDS},
            "breakdown": dict(dec.last_breakdown_ms),
            "tables": (
                compare.plain_unicast(
                    await handler.get_route_table_by_client(CLIENT_ID_OPENR)),
                compare.plain_mpls(
                    await handler.get_mpls_route_table_by_client(CLIENT_ID_OPENR)),
            ),
            "want": reference.tables(g, hand.ROOT),
            "rib_is_the_oracles": (
                dec.rib.unicast_routes == oracle.unicast_routes
                and dec.rib.mpls_routes == oracle.mpls_routes),
            "vip_routes_in_rib": sum(
                str(p.prefix).startswith("10.200.") for p in dec.rib.unicast_routes),
            # VIP 2's route objects: the area cache's and the merge book's
            "vip_2": (dec._area_cache[AREA]["rdb"].unicast_routes[VIP_2],
                      dec.rib.unicast_routes[VIP_2]),
        }

    async def event(label: str, key_vals: dict) -> dict:
        before = stats()
        rebuilds0 = {k: counters.get(k) for k in REBUILDS}
        runs = counters.get("decision.spf_runs")
        pubs.push(Publication(
            area=AREA, key_vals=key_vals,
            perf_events=perf.PerfEvents.start(perf.KVSTORE_FLOODED, node="test"),
        ))
        await until(lambda: counters.get("decision.spf_runs") > runs, "the rebuild")
        trace = await asyncio.wait_for(traces.get(), 60)
        assert trace.last_event() == perf.FIB_PROGRAMMED
        return await step(label, before, rebuilds0)

    def link_at(metric: int) -> dict:
        """Both ends' adjacency values with THE LINK at `metric`."""
        a, b = hand.THE_LINK
        g.set_metric(a, b, metric)
        out = {}
        for u, v in ((a, b), (b, a)):
            db, other = adj_dbs[u], topo.node_name(v)
            adj_dbs[u] = dataclasses.replace(db, adjacencies=tuple(
                dataclasses.replace(x, metric=metric)
                if x.other_node_name == other else x for x in db.adjacencies))
            key = C.adj_key(db.this_node_name)
            out[key] = value(key, adj_dbs[u])
        return out

    def weight_at(vip: int, advertiser: int, weight: int) -> dict:
        """The advertiser's prefix database with that VIP at `weight`,
        under the VIP's key."""
        hand.set_weight(g, vip, advertiser, weight)
        prefix = g.meta["vips"]["prefix"][vip]
        db = prefix_dbs[advertiser]
        prefix_dbs[advertiser] = dataclasses.replace(db, prefix_entries=tuple(
            dataclasses.replace(e, weight=weight)
            if str(e.prefix) == prefix else e for e in db.prefix_entries))
        key = C.prefix_key(db.this_node_name, AREA, prefix)
        return {key: value(key, prefix_dbs[advertiser])}

    out: dict = {}
    await dec.start()
    await fib.start()
    try:
        before = stats()
        rebuilds0 = {k: 0 for k in REBUILDS}
        for db, pdb in zip(adj_dbs, prefix_dbs):
            name = db.this_node_name
            kv = {C.adj_key(name): value(C.adj_key(name), db)}
            for entry in pdb.prefix_entries:
                key = C.prefix_key(name, AREA, str(entry.prefix))
                kv[key] = value(key, pdb)
            pubs.push(Publication(area=AREA, key_vals=kv))
        synced.set()
        await until(lambda: dec.rib_computed.is_set() and fib.synced.is_set(),
                    "the first RIB")
        await until(lambda: not dec.debounce._task or dec.debounce._task.done(),
                    "the rebuild coroutine's end")
        out["cold"] = await step("cold", before, rebuilds0)
        out["raised"] = await event("raised", link_at(hand.RAISED))
        out["weight"] = await event("weight", weight_at(1, 16, 6))
        out["restored"] = await event("restored", link_at(1))
    finally:
        await fib.stop()
        await dec.stop()
        for q in (pubs, routes, perf_events):
            q.close()
    return out


@pytest.fixture(scope="module")
def story():
    return asyncio.run(run_story())


STEPS = ("cold", "raised", "weight", "restored")
#: the VIPs' routes after each step, worked out by hand
BY_HAND = {
    "cold": hand.ALL_AT_1, "raised": hand.LINK_RAISED,
    "weight": hand.LINK_RAISED_VIP1_AT_6, "restored": hand.ALL_AT_1,
}


@pytest.mark.parametrize("step", STEPS)
def test_after_every_step_the_fib_is_the_references_and_the_hand_made_table(
        story, step):
    got_u, got_m = story[step]["tables"]
    want_u, want_m = story[step]["want"]
    assert compare.count_differences(got_u, want_u) == (0, [])
    assert compare.count_differences(got_m, want_m) == (0, [])
    vips = {k: v for k, v in got_u.items() if k.startswith("10.200.")}
    assert vips == hand.vip_routes(BY_HAND[step])


@pytest.mark.parametrize("step", STEPS)
def test_the_backend_is_the_scalar_oracle_byte_for_byte(story, step):
    assert story[step]["rib_is_the_oracles"]
    assert story[step]["vip_routes_in_rib"] == 6  # seven VIPs, one the root's own


def test_each_step_took_the_path_it_is_named_for(story):
    assert story["cold"]["rebuilds"]["decision.rebuild.full"] == 1
    for step in ("raised", "restored"):
        assert story[step]["rebuilds"] == {
            "decision.rebuild.full": 0, "decision.rebuild.topo_delta": 1,
            "decision.rebuild.prefix_only": 0, "decision.spf.warm_starts": 1}
    assert story["weight"]["rebuilds"] == {
        "decision.rebuild.full": 0, "decision.rebuild.topo_delta": 0,
        "decision.rebuild.prefix_only": 1, "decision.spf.warm_starts": 0}


def test_the_counters_read_the_hand_count(story):
    # cold: plain and anycast prefixes are elected in bulk; the scalar
    # election sees the four VIPs that state a weight (1, 2, 3, 5)
    assert story["cold"]["grew"] == {
        "general_prefixes": 4, "ucmp_prefixes": 4,
        "ucmp_slot_visits": hand.SLOT_VISITS_ALL_AT_1, "multi_scoped": 0,
        "complex_scoped": 0}
    # warm: ToR 14 changed: its loopback, the two anycast VIPs it
    # advertises (0 and 6: named by the advertiser matrix), and the three
    # weighted VIPs it advertises (1, 3 and 5: named by the advertiser
    # table). Not VIP 2 (advertisers 13 and 15): its two planes stay out
    # of the slot visits
    vip_2_visits = 2
    assert story["raised"]["grew"] == {
        "general_prefixes": 1 + 2 + 3, "ucmp_prefixes": 3,
        "ucmp_slot_visits": hand.SLOT_VISITS_LINK_RAISED - vip_2_visits,
        "multi_scoped": 2, "complex_scoped": 3}
    assert hand.SLOT_VISITS_LINK_RAISED - vip_2_visits == 3 + 1 + 3
    # prefix-only: VIP 1 alone; advertisers 14 (plane 0) and 16 (both)
    assert story["weight"]["grew"] == {
        "general_prefixes": 1, "ucmp_prefixes": 1, "ucmp_slot_visits": 1 + 2,
        "multi_scoped": 0, "complex_scoped": 0}
    assert story["restored"]["grew"] == {
        "general_prefixes": 1 + 2 + 3, "ucmp_prefixes": 3,
        "ucmp_slot_visits": hand.SLOT_VISITS_ALL_AT_1 - vip_2_visits,
        "multi_scoped": 2, "complex_scoped": 3}
    assert hand.SLOT_VISITS_ALL_AT_1 - vip_2_visits == 4 + 2 + 4


def test_a_weighted_vip_the_event_did_not_touch_keeps_its_route_object(story):
    """VIP 2's `RibEntry` is elected once, cold; every later RIB holds
    that object, in the area's cache and in the merge book."""
    cold = story["cold"]["vip_2"]
    assert cold[0].nexthops[0].weight == 1 and len(cold[0].nexthops) == 2
    for step in ("raised", "weight", "restored"):
        for got, first in zip(story[step]["vip_2"], cold):
            assert got is first, step


@pytest.mark.parametrize("step", STEPS)
def test_decision_exports_the_five_counters(story, step):
    # the export runs inside the rebuild, before its routes are pushed
    assert story[step]["exported"] == story[step]["held"]


def test_the_span_is_there_on_all_three_paths(story):
    assert "spf:unicast_general" in names.REBUILD_SPANS
    cold, raised, weight = (story[s]["breakdown"] for s in ("cold", "raised", "weight"))
    assert 0 < cold["spf:unicast_general"] <= cold["spf:rib_unicast"]
    assert cold["spf:warm_reassemble"] == 0
    for bd in (raised, story["restored"]["breakdown"]):
        assert 0 < bd["spf:unicast_general"] <= bd["spf:warm_reassemble"]
        assert bd["spf:rib_unicast"] == 0
    # prefix-only: no solve, no warm start, the election alone
    assert weight["spf:unicast_general"] > 0
    assert weight["spf:warm_reassemble"] == weight["spf:rib_unicast"] == 0


def test_the_span_nests_where_docs_monitor_says():
    """The span's parent on the cold and on the prefix-only path, read
    off the solver's own record (the warm path's: the story above)."""
    _adj_dbs, ls, ps = lsdb_of(hand.graph())
    solver = TpuSpfSolver(native_rib="off")
    with profiling.collect() as rec:
        _rdb, art = solver.compute_routes(
            ls, ps, topo.node_name(hand.ROOT), return_artifact=True)
    parents = {name: parent for name, parent, _s, _e in rec.spans}
    assert parents["spf:unicast_general"] == "spf:rib_unicast"
    with profiling.collect() as rec:
        solver.assemble_prefix_routes(art, ps, set(ps.prefixes))
    assert [(n, p) for n, p, _s, _e in rec.spans if not n.endswith(":gc")] == [
        ("spf:general_items", None), ("spf:unicast_general", None)]
    # every prefix went down the scalar path here: 19 loopbacks (the
    # root's own is local), 7 VIPs
    assert solver.spf_kernel_stats["general_prefixes"] == 4 + 20 + 7


def twin_graph() -> topo.Graph:
    spec = Path(__file__).resolve().parents[1] / "perfbench" / "configs"
    return topo.build(
        json.loads((spec / "tiny_fabric_vips.json").read_text())["topology"])


@pytest.mark.parametrize("pod,agg,tor", [(1, 0, 0), (2, 1, 1), (3, 0, 1)])
def test_on_seeded_weights_a_warm_start_is_the_scalar_oracle_byte_for_byte(
        pod, agg, tor):
    """The twin's graph (weights drawn from `graph_seed`): a ToR uplink
    raised and restored through `warm_compute_routes` itself, each result
    against a from-scratch scalar oracle over the same LSDB."""
    g = twin_graph()
    me = topo.node_name(topo.fat_tree_tor(g, 0, 0))
    adj_dbs, ls, ps = lsdb_of(g)
    solver = TpuSpfSolver(native_rib="off")
    rdb, art = solver.compute_routes(ls, ps, me, return_artifact=True)
    a, t = topo.fat_tree_agg(g, pod, agg), topo.fat_tree_tor(g, pod, tor)
    for metric in (10, 1):
        pairs = []
        for u, v in ((a, t), (t, a)):
            db, other = adj_dbs[u], topo.node_name(v)
            adj_dbs[u] = dataclasses.replace(db, adjacencies=tuple(
                dataclasses.replace(x, metric=metric)
                if x.other_node_name == other else x for x in db.adjacencies))
            changed, delta = ls.update_adjacency_db_delta(adj_dbs[u])
            assert changed and delta is not None
            pairs += delta
        warm0 = solver.warm_solves
        res = solver.warm_compute_routes(art, ls, ps, me, pairs, set(), rdb, 0.25)
        assert res is not None and solver.warm_solves == warm0 + 1
        art0, prev = art, rdb
        rdb, art, touched, _labels, _region = res
        oracle = oracle_compute_routes(ls, ps, me, vectorize=False)
        assert rdb.unicast_routes == oracle.unicast_routes, metric
        assert rdb.mpls_routes == oracle.mpls_routes, metric
        # the link's ToR is the one node whose (distance, first hops)
        # moved, read off the two solves
        names = ls.to_csr().node_names
        n = len(names)
        moved = (art0.solved[1][:n, 0] != art.solved[1][:n, 0]) | (
            art0.solved[2][:, :n] != art.solved[2][:, :n]).any(axis=0)
        assert [names[i] for i in moved.nonzero()[0]] == [topo.node_name(t)]
        # of the weighted VIPs exactly those that ToR advertises are
        # re-elected; the others keep the cached route object
        weighted = {p for p in ps.prefixes if any(
            e.weight for e in ps.prefixes[p].values())}
        its_own = {p for p in weighted if topo.node_name(t) in ps.prefixes[p]}
        assert len(weighted) == 8 and weighted & touched == its_own
        # ToR (1, 0) advertises no weighted VIP, the other two cases' do
        assert len(its_own) < 8 and bool(its_own) == ((pod, tor) != (1, 0))
        for p in weighted - its_own:
            assert rdb.unicast_routes.get(p) is prev.unicast_routes.get(p)
