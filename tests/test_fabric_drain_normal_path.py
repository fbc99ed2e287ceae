"""Drain and undrain through the normal path at CPU size (configuration
`fabric_drain`, PR 38): publication -> Decision(solver="tpu") -> structural
rebuild -> Fib -> handler on a k = 6 fabric, held to the plain reference
(`perfbench/references/fabric_drain.py`, which imports nothing of the
program) and to the scalar oracle byte for byte. What the deployment added
to the program:

  * the spans `spf:table_build` and `spf:upload`, children of
    `spf:dispatch` in `TpuSpfSolver._device_arrays`;
  * the counters `decision.dev_cache.upload_bytes` and
    `decision.rebuild.structural`; since PR 39 `decision.csr.builds` and
    `decision.csr.overload_rebases` (an overload bit re-bases the CSR).

One story (module fixture): the first RIB with one aggregation switch and
one spine drained; an aggregation switch of another pod drained; a ToR
uplink raised (a warm start on the drained base); that switch undrained;
then a neighbour of the root drained and undrained, and six more events
on other switches, for the device tables of bases that are gone.
"""

import asyncio
import dataclasses
import time

import pytest

from openr_tpu.common import constants as C
from openr_tpu.config import Config
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.fib import Fib, MockFibHandler
from openr_tpu.fib.fib import CLIENT_ID_OPENR
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters, compile_ledger, names, perf
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.serde import to_wire
from perfbench import compare, topo
from perfbench.drivers.decision_fib import AREA, program_dbs
from perfbench.references import fabric_drain as reference
from perfbench.topologies import fat_tree_drained

K = 6
COUNTED = ("decision.rebuild.full", "decision.rebuild.structural",
           "decision.rebuild.topo_delta", "decision.spf.warm_starts",
           "decision.dev_cache.uploads", "decision.dev_cache.upload_bytes",
           "decision.spf.engine_native", "decision.csr.builds",
           "decision.csr.overload_rebases")


async def run_story() -> dict:
    g = fat_tree_drained.build(K, 1, 1, 0)
    root = topo.fat_tree_tor(g, 0, 0)
    me = topo.node_name(root)
    adj_dbs, prefix_dbs = program_dbs(g)
    drained = set(g.meta["drained"])
    for node in drained:
        adj_dbs[node] = dataclasses.replace(adj_dbs[node], is_overloaded=True)
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

    def value(db) -> Value:
        name = db.this_node_name
        versions[name] = versions.get(name, 0) + 1
        return Value(version=versions[name], originator_id=name,
                     value=to_wire(db)).with_hash()

    async def until(pred, what):
        deadline = time.monotonic() + 120
        while not pred():
            assert dec.last_rebuild_error is None, dec.last_rebuild_error
            assert time.monotonic() < deadline, f"timed out waiting for {what}"
            await asyncio.sleep(0.002)

    def counted() -> dict:
        return {k: counters.get(k) or 0 for k in COUNTED}

    async def step(label: str, before: dict, compiles0: int) -> dict:
        """What the rebuild that just reached Fib did and left."""
        after = counted()
        ls, = dec.link_states.values()
        ps, = dec.prefix_states.values()
        oracle = oracle_compute_routes(ls, ps, me, vectorize=False)
        split = dec._tpu._dev[next(reversed(dec._tpu._dev))]["sets"]["split"]
        return {
            "label": label,
            "grew": {k: after[k] - before[k] for k in COUNTED},
            "compiled": compile_ledger.ledger().snapshot().total - compiles0,
            "breakdown": dict(dec.last_breakdown_ms),
            "tables": (
                compare.plain_unicast(
                    await handler.get_route_table_by_client(CLIENT_ID_OPENR)),
                compare.plain_mpls(
                    await handler.get_mpls_route_table_by_client(CLIENT_ID_OPENR)),
            ),
            "want": reference.tables(fat_tree_drained.as_published(g, drained), root),
            "rib_is_the_oracles": (
                dec.rib.unicast_routes == oracle.unicast_routes
                and dec.rib.mpls_routes == oracle.mpls_routes),
            "table_bytes": sum(
                int(v.nbytes) for v in split.values() if hasattr(v, "nbytes")),
            "bases_on_the_device": len(dec._tpu._dev),
            # the solver's ids follow the sorted names: back to the graph's
            "over_on_the_device": sorted(
                int(ls.to_csr().node_names[int(i)].rpartition("-")[2])
                for i in split["over"].nonzero()[0]),
        }

    async def event(label: str, dbs: list) -> dict:
        before = counted()
        compiles0 = compile_ledger.ledger().snapshot().total
        runs = counters.get("decision.spf_runs")
        pubs.push(Publication(
            area=AREA,
            key_vals={C.adj_key(db.this_node_name): value(db) for db in dbs},
            perf_events=perf.PerfEvents.start(perf.KVSTORE_FLOODED, node="test"),
        ))
        await until(lambda: counters.get("decision.spf_runs") > runs, "the rebuild")
        trace = await asyncio.wait_for(traces.get(), 60)
        assert trace.last_event() == perf.FIB_PROGRAMMED
        await until(lambda: not dec.debounce._task or dec.debounce._task.done(),
                    "the rebuild coroutine's end")
        return await step(label, before, compiles0)

    def overload(node: int, bit: bool) -> list:
        """The switch's own database with the bit at `bit`, nothing else
        changed: what `set_node_overload` re-advertises."""
        (drained.add if bit else drained.remove)(node)
        adj_dbs[node] = dataclasses.replace(adj_dbs[node], is_overloaded=bit)
        return [adj_dbs[node]]

    def uplink_at(metric: int) -> list:
        """Both ends' databases with the link ToR (1, 0) <-> agg (1, 1) at
        `metric`."""
        tor, agg = topo.fat_tree_tor(g, 1, 0), topo.fat_tree_agg(g, 1, 1)
        g.set_metric(tor, agg, metric)
        for u, v in ((tor, agg), (agg, tor)):
            adj_dbs[u] = dataclasses.replace(adj_dbs[u], adjacencies=tuple(
                dataclasses.replace(x, metric=metric)
                if x.other_node_name == topo.node_name(v) else x
                for x in adj_dbs[u].adjacencies))
        return [adj_dbs[tor], adj_dbs[agg]]

    out: dict = {"drained_at_first": sorted(drained), "root": root}
    await dec.start()
    await fib.start()
    try:
        before = counted()
        compiles0 = compile_ledger.ledger().snapshot().total
        for db, pdb in zip(adj_dbs, prefix_dbs):
            name = db.this_node_name
            kv = {C.adj_key(name): value(db)}
            for entry in pdb.prefix_entries:
                kv[C.prefix_key(name, AREA, str(entry.prefix))] = Value(
                    version=1, originator_id=name, value=to_wire(pdb)).with_hash()
            pubs.push(Publication(area=AREA, key_vals=kv))
        synced.set()
        await until(lambda: dec.rib_computed.is_set() and fib.synced.is_set(),
                    "the first RIB")
        await until(lambda: not dec.debounce._task or dec.debounce._task.done(),
                    "the rebuild coroutine's end")
        out["cold"] = await step("cold", before, compiles0)
        # an aggregation switch of a pod that holds no drained one
        standing_pod = max(
            fat_tree_drained.pod_of_agg(g, n) for n in drained)
        pod = next(p for p in range(1, K) if p != standing_pod)
        agg = topo.fat_tree_agg(g, pod, 0)
        out["pod"], out["agg"] = pod, agg
        out["drained"] = await event("drained", overload(agg, True))
        out["raised"] = await event("raised", uplink_at(10))
        out["undrained"] = await event("undrained", overload(agg, False))
        own = topo.fat_tree_agg(g, 0, 1)
        out["own_drained"] = await event("own_drained", overload(own, True))
        out["own_undrained"] = await event("own_undrained", overload(own, False))
        more = [n for n in g.meta["drain_pool"].tolist()
                if fat_tree_drained.pod_of_agg(g, n) not in (pod, standing_pod)][:3]
        for i, node in enumerate(more):
            out[f"more_{2 * i}"] = await event("more", overload(node, True))
            out[f"more_{2 * i + 1}"] = await event("more", overload(node, False))
    finally:
        await fib.stop()
        await dec.stop()
        for q in (pubs, routes, perf_events):
            q.close()
    return out


@pytest.fixture(scope="module")
def story():
    return asyncio.run(run_story())


STRUCTURAL = ("drained", "undrained", "own_drained", "own_undrained",
              *(f"more_{i}" for i in range(6)))
STEPS = ("cold", "drained", "raised", "undrained", "own_drained",
         "own_undrained", "more_5")


@pytest.mark.parametrize("step", STEPS)
def test_after_every_step_the_fib_is_the_references(story, step):
    got_u, got_m = story[step]["tables"]
    want_u, want_m = story[step]["want"]
    assert compare.count_differences(got_u, want_u) == (0, [])
    assert compare.count_differences(got_m, want_m) == (0, [])
    assert len(want_u) == 5 * K * K // 4 - 1


@pytest.mark.parametrize("step", STEPS)
def test_the_backend_is_the_scalar_oracle_byte_for_byte(story, step):
    assert story[step]["rib_is_the_oracles"]


def test_a_drain_moves_the_routes_of_one_pods_tors_through_one_plane(story):
    """Plane 0 toward the pod dies: its ToRs' loopbacks and labels lose
    the next hop agg (0, 0), and nothing else moves; the undrain puts the
    first table back."""
    cold_u, cold_m = story["cold"]["tables"]
    got_u, got_m = story["drained"]["tables"]
    n_u, _ = compare.count_differences(got_u, cold_u)
    n_m, _ = compare.count_differences(got_m, cold_m)
    assert (n_u, n_m) == (K // 2, K // 2)
    g = topo.fat_tree(K)
    dead = topo.node_name(topo.fat_tree_agg(g, 0, 0))
    for t in range(K // 2):
        key = topo.loopback(topo.fat_tree_tor(g, story["pod"], t))
        assert {nh[0] for nh in cold_u[key]} - {nh[0] for nh in got_u[key]} == {dead}
    # the drained switch itself stays a destination, by its own plane
    assert {nh[0] for nh in got_u[topo.loopback(story["agg"])]} == {dead}
    # raised on the drained base, then undrained: the raise is all that is left
    back_u, _back_m = story["undrained"]["tables"]
    raised_tor = topo.loopback(topo.fat_tree_tor(g, 1, 0))
    assert {k for k in cold_u if cold_u[k] != back_u[k]} <= {raised_tor}


def test_a_drained_neighbour_of_the_root_is_a_next_hop_toward_itself_only(story):
    got_u, _ = story["own_drained"]["tables"]
    own = topo.node_name(topo.fat_tree_agg(topo.fat_tree(K), 0, 1))
    via = {key for key, nhs in got_u.items() if own in {nh[0] for nh in nhs}}
    assert via == {topo.loopback(topo.fat_tree_agg(topo.fat_tree(K), 0, 1))}
    assert len(got_u) == 5 * K * K // 4 - 1, "every switch stays reachable"


def test_each_step_took_the_path_it_is_named_for(story):
    # the first RIB's dirt is structural too: every key is new
    assert story["cold"]["grew"]["decision.rebuild.structural"] == 1
    assert story["cold"]["grew"]["decision.csr.builds"] == 1
    for step in STRUCTURAL:
        grew = story[step]["grew"]
        assert grew["decision.rebuild.full"] == 1, step
        assert grew["decision.rebuild.structural"] == 1, step
        assert grew["decision.dev_cache.uploads"] == 1, step
        assert grew["decision.spf.warm_starts"] == 0, step
        # an overload bit re-bases the CSR: no walk over the adjacencies
        assert grew["decision.csr.overload_rebases"] == 1, step
        assert grew["decision.csr.builds"] == 0, step
    raised = story["raised"]["grew"]
    assert raised["decision.rebuild.topo_delta"] == 1
    assert raised["decision.spf.warm_starts"] == 1
    assert raised["decision.rebuild.structural"] == 0
    assert raised["decision.dev_cache.uploads"] == 0
    assert raised["decision.dev_cache.upload_bytes"] == 0
    assert raised["decision.csr.builds"] == 0
    assert raised["decision.csr.overload_rebases"] == 0
    assert all(story[s]["grew"]["decision.spf.engine_native"] == 0 for s in STEPS)
    assert {"decision.rebuild.structural", "decision.csr.builds",
            "decision.csr.overload_rebases"} <= names.COUNTERS


def test_every_new_base_uploads_its_tables_bytes_and_both_spans_say_so(story):
    assert {"spf:table_build", "spf:upload"} < set(names.REBUILD_SPANS)
    for step in ("cold", *STRUCTURAL):
        bd, grew = story[step]["breakdown"], story[step]["grew"]
        assert grew["decision.dev_cache.upload_bytes"] == story[step]["table_bytes"]
        assert bd["spf:table_build"] > 0 and bd["spf:upload"] > 0, step
        assert bd["spf:table_build"] + bd["spf:upload"] <= bd["spf:dispatch"], step
        assert bd["spf:dispatch"] <= bd["spf:prepare"] <= bd["compute_rib"], step
        assert bd["spf:warm_solve"] == 0 and bd["spf:batched_solve"] > 0, step
    # the same shapes for every base: an overload bit moves no table
    assert len({story[s]["table_bytes"] for s in ("cold", *STRUCTURAL)}) == 1
    bd = story["raised"]["breakdown"]
    assert bd["spf:table_build"] == 0 == bd["spf:upload"]
    assert bd["spf:warm_solve"] > 0


def test_the_mask_on_the_device_is_the_drained_set_of_its_base(story):
    assert story["cold"]["over_on_the_device"] == story["drained_at_first"]
    assert story["drained"]["over_on_the_device"] == sorted(
        [*story["drained_at_first"], story["agg"]])
    assert story["undrained"]["over_on_the_device"] == story["drained_at_first"]


def test_no_event_after_the_first_rib_compiles_and_old_bases_leave_the_device(story):
    """One `has_overloads=True` program from the first solve to the last
    (the standing set), the warm path's pre-warmed after the first RIB;
    `_dev` is an LRU of four bases whatever the number of events."""
    # the ledger counts in this process (by the first RIB at the latest; a
    # test before this one may have compiled the same programs)
    assert compile_ledger.ledger().snapshot().total > 0
    for step in (*STRUCTURAL, "raised"):
        assert story[step]["compiled"] == 0, step
    assert story["more_5"]["bases_on_the_device"] == 4
    assert max(story[s]["bases_on_the_device"] for s in STRUCTURAL) == 4
