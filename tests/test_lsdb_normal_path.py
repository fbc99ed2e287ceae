"""The 100k-node LSDB's path at CPU size (configuration `lsdb100k`, PR 28):
publication -> Decision(solver="tpu") -> warm start -> Fib -> handler over
the benchmark's own ER generator, held to the plain reference
(`perfbench/reference.py`, which imports nothing of the program), and the
three things the deployment added to the program:

  * `decision.rebuild.no_change`: a rebuild that proves no route change is
    counted and tells Fib nothing;
  * `TpuSpfSolver.prewarm_flap_programs`: after the first RIB no event of
    any kind (base patch, overflow patch, cone, cost-in) compiles, and
    nothing the solver holds differs from a run with the pre-warm stubbed;
  * the spans `decision:snapshot` and `spf:prewarm`.

One story of seeded cost-out / cost-in events is run twice (module
fixture), with the pre-warm and with it stubbed out; the cases read it.
"""

import asyncio
import dataclasses
import time

import jax
import numpy as np
import pytest

from openr_tpu.common import constants as C
from openr_tpu.config import Config
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.fib import Fib, MockFibHandler
from openr_tpu.fib.fib import CLIENT_ID_OPENR
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters, compile_ledger, names, perf, profiling
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.serde import to_wire
from perfbench import compare, reference, topo
from perfbench.drivers.decision_fib import AREA, program_dbs
from perfbench.events import link_pool

ROOT, HUB, N = 0, 5, 300
TABLES = ("base_nbr", "base_wgt", "ov_ids", "ov_nbr", "ov_wgt", "out_nbr", "over")
KINDS = ("base_patch", "overflow_patch", "cone", "cost_in", "no_change", "change")


def hub_graph() -> topo.Graph:
    """The twin's generator (`tiny_lsdb`: 300 nodes, degree 6, metrics
    1..64) plus one hub of 40 more links, so that the split tables have an
    overflow row and a patch can land there."""
    g = topo.erdos_renyi(N, 6, 64, 0)
    rng = np.random.default_rng(1)
    have = set(zip(g.src.tolist(), g.dst.tolist()))
    far = [v for v in rng.permutation(N).tolist()
           if v not in (HUB, ROOT) and (HUB, v) not in have][:40]
    m = rng.integers(1, 65, len(far))
    src = np.concatenate([g.src, np.full(len(far), HUB), far])
    dst = np.concatenate([g.dst, far, np.full(len(far), HUB)])
    met = np.concatenate([g.metric, m, m])
    order = np.argsort(dst, kind="stable")
    return topo.Graph(N, src[order].astype(np.int64),
                      dst[order].astype(np.int64), met[order].astype(np.int64))


def changes_routes(g: topo.Graph, link, metric: int) -> bool:
    """By the reference alone: does ROOT's table move when `link` goes to
    `metric`?"""
    g2 = g.copy()
    g2.set_metric(*link, metric)
    g2.set_metric(*link[::-1], metric)
    return reference.tables(g, ROOT) != reference.tables(g2, ROOT)


def the_story(g: topo.Graph) -> list[tuple[tuple[int, int], int]]:
    """(link, metric) events, picked by the reference: cost-outs that do
    and do not move ROOT's routes, some at the hub, each costed in again."""
    pool = [tuple(map(int, row)) for row in link_pool(g, "any_not_at_root", ROOT)]
    plain = [ln for ln in pool if HUB not in ln]
    at_hub = [ln for ln in pool if HUB in ln][::7][:3]
    moving = next(ln for ln in plain if changes_routes(g, ln, 64))
    still = next(ln for ln in plain if not changes_routes(g, ln, 64))
    out = [(moving, 64), (still, 64)] + [(ln, 64) for ln in at_hub]
    return out + [(ln, 1) for ln, _ in out]


async def run_story() -> dict:
    """First RIB, then the story, one event in flight; what each event
    did, and what the solver held after the first RIB and at the end."""
    g = hub_graph()
    events = the_story(g)
    adj_dbs, prefix_dbs = program_dbs(g)
    me = topo.node_name(ROOT)
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
    versions = dict.fromkeys((db.this_node_name for db in adj_dbs), 1)
    led = compile_ledger.ledger()

    def value(db):
        return Value(version=versions[db.this_node_name],
                     originator_id=db.this_node_name,
                     value=to_wire(db)).with_hash()

    async def until(pred, what):
        deadline = time.monotonic() + 120
        while not pred():
            assert dec.last_rebuild_error is None, dec.last_rebuild_error
            assert time.monotonic() < deadline, f"timed out waiting for {what}"
            await asyncio.sleep(0.002)

    async def tables():
        return (
            compare.plain_unicast(
                await handler.get_route_table_by_client(CLIENT_ID_OPENR)),
            compare.plain_mpls(
                await handler.get_mpls_route_table_by_client(CLIENT_ID_OPENR)),
        )

    def held():
        """What a pre-warm could have touched, as host arrays."""
        (cache,) = dec._area_cache.values()
        csr, dist, fh, nbr_ids, _lfa = cache["art"].solved
        dev = dec._tpu._dev[csr.base_version]["sets"]["split"]
        return {
            "rib": (dict(dec.rib.unicast_routes), dict(dec.rib.mpls_routes)),
            "dist": np.asarray(dist).copy(), "fh": fh.copy(),
            "nbr_ids": list(nbr_ids),
            "dev": {t: np.asarray(dev[t]) for t in TABLES},
        }

    def patch_tables(n_patches: int) -> set[str]:
        """Which split table each of the journal's last patches fell in."""
        (cache,) = dec._area_cache.values()
        csr = cache["art"].solved[0]
        w = dec._tpu._dev[csr.base_version]["host"]["split"]["base_w"]
        return {"base" if p.dense_col < w else "overflow"
                for p in csr.patches[-n_patches:]}

    out: dict = {"events": []}
    jax.clear_caches()  # so that every program this story needs compiles in it
    await dec.start()
    await fib.start()
    try:
        for db, pdb in zip(adj_dbs, prefix_dbs):
            kv = {C.adj_key(db.this_node_name): value(db)}
            for entry in pdb.prefix_entries:
                kv[C.prefix_key(db.this_node_name, AREA, str(entry.prefix))] = (
                    Value(version=1, originator_id=db.this_node_name,
                          value=to_wire(pdb)).with_hash())
            pubs.push(Publication(area=AREA, key_vals=kv))
        synced.set()
        await until(lambda: dec.rib_computed.is_set() and fib.synced.is_set(),
                    "the first RIB")
        # the pre-warm follows the publish in the same coroutine, whose
        # end (and not rib_computed) is where the breakdown holds it
        await until(lambda: not dec.debounce._task or dec.debounce._task.done(),
                    "the rebuild coroutine's end")
        out["first_breakdown"] = dict(dec.last_breakdown_ms)
        out["first"] = held()
        out["first_tables"] = await tables()
        out["first_want"] = reference.tables(g, ROOT)
        out["prewarm_programs"] = counters.get("decision.spf.prewarm_programs")
        out["no_change_at_first"] = counters.counters.get(
            "decision.rebuild.no_change")
        for (a, b), metric in events:
            before = reference.tables(g, ROOT)
            changed = []
            for u, v in ((a, b), (b, a)):
                g.set_metric(u, v, metric)
                db, other = adj_dbs[u], topo.node_name(v)
                adj_dbs[u] = dataclasses.replace(db, adjacencies=tuple(
                    dataclasses.replace(x, metric=metric)
                    if x.other_node_name == other else x
                    for x in db.adjacencies))
                versions[db.this_node_name] += 1
                changed.append(adj_dbs[u])
            want = reference.tables(g, ROOT)
            runs = counters.get("decision.spf_runs")
            none0 = counters.get("decision.rebuild.no_change")
            ops0 = handler.op_count
            cells0 = dec._tpu.spf_kernel_stats["warm_cone_cells"]
            mark = led.snapshot()
            pubs.push(Publication(
                area=AREA,
                key_vals={C.adj_key(db.this_node_name): value(db)
                          for db in changed},
                perf_events=perf.PerfEvents.start(
                    perf.KVSTORE_FLOODED, node="test"),
            ))
            await until(lambda: counters.get("decision.spf_runs") > runs,
                        "the rebuild")
            none = counters.get("decision.rebuild.no_change") - none0
            if none:
                await asyncio.sleep(0.05)  # Fib's turn, had it been told
            else:
                trace = await asyncio.wait_for(traces.get(), 60)
                assert trace.last_event() == perf.FIB_PROGRAMMED
            out["events"].append({
                "link": (a, b), "metric": metric,
                "reference_moved": before != want,
                "no_change_counted": none,
                "fib_ops": handler.op_count - ops0,
                "tables_right": await tables() == want,
                "compiled": mark.delta(led.snapshot()),
                "patched": patch_tables(2),
                "cone_cells":
                    dec._tpu.spf_kernel_stats["warm_cone_cells"] - cells0,
            })
        out["last"] = held()
        out["counters"] = dict(counters.counters)
        out["prewarm_programs_last"] = counters.get(
            "decision.spf.prewarm_programs")
    finally:
        await fib.stop()
        await dec.stop()
        for q in (pubs, routes, perf_events):
            q.close()
    return out


@pytest.fixture(scope="module")
def stories():
    assert compile_ledger.ledger().installed
    with_prewarm = asyncio.run(run_story())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(TpuSpfSolver, "prewarm_flap_programs", lambda self, art: 0)
        stubbed = asyncio.run(run_story())
    return with_prewarm, stubbed


def of_kind(events: list[dict], kind: str) -> list[dict]:
    return [e for e in events if {
        "base_patch": "base" in e["patched"],
        "overflow_patch": "overflow" in e["patched"],
        "cone": e["cone_cells"] > 0,
        "cost_in": e["metric"] == 1,
        "no_change": not e["reference_moved"],
        "change": e["reference_moved"],
    }[kind]]


def test_the_first_rib_equals_the_reference_and_the_prewarm_ran(stories):
    story, stubbed = stories
    assert story["first_tables"] == story["first_want"]
    assert len(story["first_want"][0]) == N - 1
    # the base has an overflow row, so all four programs: two table
    # scatters, the cone's, the warm kernel
    assert story["prewarm_programs"] == 4
    assert story["first_breakdown"]["spf:prewarm"] > 0.0
    # registered from the first rebuild on, at 0 (set at export)
    assert story["no_change_at_first"] == 0
    assert stubbed["prewarm_programs"] == 0
    assert stubbed["first_breakdown"]["spf:prewarm"] == 0.0


@pytest.mark.parametrize("kind", KINDS)
def test_an_event_of_each_kind_programs_the_references_tables(stories, kind):
    """After every event the handler's tables are the reference's for the
    graph as published; an event that moves no route (by the reference) is
    counted once under `decision.rebuild.no_change` and tells Fib nothing,
    one that does is not counted and reaches the handler."""
    events = of_kind(stories[0]["events"], kind)
    assert events, f"the story has no {kind} event"
    for e in events:
        assert e["tables_right"], e
        if e["reference_moved"]:
            assert e["no_change_counted"] == 0 and e["fib_ops"] > 0, e
        else:
            assert e["no_change_counted"] == 1 and e["fib_ops"] == 0, e


@pytest.mark.parametrize("kind", KINDS)
def test_after_the_prewarm_an_event_of_each_kind_compiles_nothing(stories, kind):
    story, stubbed = stories
    for e in of_kind(story["events"], kind):
        assert e["compiled"] == {}, e
    # the control: the same events with the pre-warm stubbed out do
    # compile, the rare variants late (the first cone, the first patch of
    # the overflow table), which no run of quiet events can promise
    late = [e for e in stubbed["events"] if e["compiled"]]
    assert late[0] is stubbed["events"][0]
    assert "batched_sssp_split_warm_rib" in late[0]["compiled"]
    assert len(late) >= 2
    assert all(set(e["compiled"]) == {"_scatter_set"} for e in late[1:])


def test_a_metric_only_life_prewarms_once(stories):
    story, _ = stories
    assert story["prewarm_programs_last"] == story["prewarm_programs"]
    assert story["counters"]["decision.rebuild.full"] == 1
    assert story["counters"]["decision.spf.warm_fallbacks"] == 0
    assert story["counters"]["decision.rebuild.topo_delta"] == len(story["events"])


@pytest.mark.parametrize("when", ["first", "last"])
def test_the_prewarm_changes_nothing_the_solver_holds(stories, when):
    """RIB, artifact (distance matrix, first hops) and device tables are
    equal, cell for cell, with the pre-warm and with it stubbed out: after
    the first RIB and after the story's last event."""
    a, b = stories[0][when], stories[1][when]
    assert a["rib"] == b["rib"] and len(a["rib"][0]) == N - 1
    assert a["nbr_ids"] == b["nbr_ids"]
    np.testing.assert_array_equal(a["dist"], b["dist"])
    np.testing.assert_array_equal(a["fh"], b["fh"])
    for t in TABLES:
        np.testing.assert_array_equal(a["dev"][t], b["dev"][t], err_msg=t)
    if when == "first":
        assert stories[0]["first_tables"] == stories[1]["first_tables"]


def grid_decision(side: int):
    """A Decision(solver="tpu") of node-0 on a side x side grid, fed by
    hand (no queue, no Fib), and the grid's databases."""
    from openr_tpu.utils import topogen

    cfg = Config.default("node-0")
    cfg.node.decision.native_rib = "off"
    d = Decision(cfg, ReplicateQueue(name="p").get_reader(),
                 ReplicateQueue(name="r"), solver="tpu", counters=Counters())
    return (d, *topogen.grid(side, side, metric=10))


def grid_pub(dbs, key, version: int) -> Publication:
    return Publication(area=C.DEFAULT_AREA, key_vals={
        key(db): Value(version=version, originator_id=db.this_node_name,
                       value=to_wire(db)).with_hash() for db in dbs})


def adj_key_of(db) -> str:
    return C.adj_key(db.this_node_name)


def test_a_structural_change_with_the_same_shapes_prewarms_nothing_more():
    async def body():
        d, adj_dbs, prefix_dbs = grid_decision(5)
        d.process_publication(grid_pub(adj_dbs, adj_key_of, 1))
        d.process_publication(grid_pub(
            prefix_dbs,
            lambda db: C.prefix_key(db.this_node_name, C.DEFAULT_AREA,
                                    str(db.prefix_entries[0].prefix)), 1))
        await d._rebuild_routes()
        ran = d.counters.get("decision.spf.prewarm_programs")
        # node-24 loses its link to node-23: a new base, the same shapes
        cut = []
        for db in adj_dbs:
            if db.this_node_name in ("node-23", "node-24"):
                other = {"node-23": "node-24", "node-24": "node-23"}[db.this_node_name]
                cut.append(dataclasses.replace(db, adjacencies=tuple(
                    a for a in db.adjacencies if a.other_node_name != other)))
        d.process_publication(grid_pub(cut, adj_key_of, 2))
        await d._rebuild_routes()
        return d, ran

    d, ran = asyncio.run(body())
    assert ran >= 3
    assert d.counters.get("decision.rebuild.full") == 2
    assert d.counters.get("decision.spf.prewarm_programs") == ran
    assert d.last_breakdown_ms["spf:prewarm"] == 0.0


def test_the_snapshot_span_nests_under_apply_snapshot(stories):
    async def body():
        d, adj_dbs, _ = grid_decision(4)
        d.process_publication(grid_pub(adj_dbs, adj_key_of, 1))
        with profiling.collect() as rec:
            await d._rebuild_routes()
        return d, rec

    d, rec = asyncio.run(body())
    parents = {n: p for n, p, _s, _e in rec.spans}
    assert parents["decision:snapshot"] == "decision:apply_snapshot"
    assert parents["spf:prewarm"] is None  # after decision:rebuild, beside it
    bd = d.last_breakdown_ms
    assert 0.0 < bd["decision:snapshot"] <= bd["apply_snapshot"]
    assert {"decision:snapshot", "spf:prewarm"} < set(names.REBUILD_SPANS)
    # and in the benchmark's story, every event's rebuild carried it
    assert stories[0]["first_breakdown"]["decision:snapshot"] > 0.0
