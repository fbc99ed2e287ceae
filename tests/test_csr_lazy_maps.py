"""A built CsrGraph's maps over arrays, and the build's columns kept per
adjacency database (`decision.csr.databases_converted`), at k = 4:

- a link down and back up converts the two databases that changed and
  walks every adjacency; a metric patch and a re-base convert none;
- through Decision, the counter reads 2 a link event and 0 a metric
  change or a drain, and no reader of the rebuild iterates a built
  graph's `adj_details` or `edge_index` (a key of every edge);
- a hand-built CsrGraph with a plain-dict `adj_details` (er100k's
  `perfbench/drivers/solver_rebuild.program_lsdb`) solves as the plain
  reference does.
"""

import asyncio
import dataclasses
import time

import pytest

from openr_tpu.common import constants as C
from openr_tpu.config import Config
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.linkstate import EdgeDetails, EdgeIndex, LinkState
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.fib import Fib, MockFibHandler
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters, names
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.serde import to_wire
from perfbench import compare, reference, topo
from perfbench.drivers.decision_fib import AREA, program_dbs
from perfbench.drivers.solver_rebuild import program_lsdb

K = 4
COUNTER = "decision.csr.databases_converted"


def fabric():
    g = topo.fat_tree(K)
    dbs, prefix_dbs = program_dbs(g)
    return g, dbs, prefix_dbs


def without(db, peer: str):
    """`db` with its adjacency to `peer` withdrawn."""
    return dataclasses.replace(db, adjacencies=tuple(
        a for a in db.adjacencies if a.other_node_name != peer))


def tor_uplink(g) -> tuple[int, int]:
    tor = topo.fat_tree_tor(g, 1, 0)
    return tor, topo.fat_tree_agg(g, 1, 1)


def test_the_counter_is_registered():
    assert COUNTER in names.COUNTERS


@pytest.mark.parametrize("which", ["tor_agg", "agg_spine"])
def test_a_link_down_and_up_converts_the_two_databases_and_walks_all(which):
    g, dbs, _ = fabric()
    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_db(db)
    ls.to_csr()
    assert ls.csr_stats["databases_converted"] == len(dbs) == 20
    u, v = (tor_uplink(g) if which == "tor_agg"
            else (topo.fat_tree_agg(g, 2, 0), 0))
    assert topo.node_name(v) in {a.other_node_name for a in dbs[u].adjacencies}
    for ends in ([without(dbs[u], topo.node_name(v)),
                  without(dbs[v], topo.node_name(u))], [dbs[u], dbs[v]]):
        before = dict(ls.csr_stats)
        for db in ends:
            ls.update_adjacency_db(db)
        csr = ls.to_csr()
        grew = {k: ls.csr_stats[k] - before[k] for k in before}
        lsdb = sum(len(ls.adjacency_db(n).adjacencies) for n in ls.nodes)
        assert grew == {"builds": 1, "overload_rebases": 0,
                        "adjacencies_walked": lsdb,
                        "databases_converted": 2, "interning_kept": 1}
        assert csr.num_edges == lsdb
    assert lsdb == 64


def test_a_snapshot_builds_from_the_shared_columns():
    """Builds run on snapshots: what one converted, the next reuses."""
    g, dbs, _ = fabric()
    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_db(db)
    ls.snapshot().to_csr()
    tor, agg = tor_uplink(g)
    ls.update_adjacency_db(without(dbs[tor], topo.node_name(agg)))
    ls.snapshot().to_csr()
    assert ls.csr_stats["builds"] == 2
    assert ls.csr_stats["databases_converted"] == 20 + 1


# ------------------------------------------------------- through Decision


def _raise(self):
    raise AssertionError(f"a rebuild iterated a built graph's {type(self)}")


async def decision_story() -> list[tuple[str, int, bool]]:
    """(event, databases converted, FIB is the oracle's) an event, after
    the first RIB."""
    g, adj_dbs, prefix_dbs = fabric()
    me = topo.node_name(topo.fat_tree_tor(g, 0, 0))
    cfg = Config.default(me)
    cfg.node.decision.native_rib = "off"
    counters = Counters()
    pubs = ReplicateQueue(name="pubs")
    routes = ReplicateQueue(name="routes")
    perf_events = ReplicateQueue(name="perf_events")
    synced = asyncio.Event()
    dec = Decision(cfg, pubs.get_reader(), routes, solver="tpu",
                   counters=counters, initial_sync_event=synced)
    fib = Fib(cfg, routes.get_reader(), MockFibHandler(),
              perf_events_queue=perf_events, counters=counters)
    versions: dict[str, int] = {}

    def value(db) -> Value:
        name = db.this_node_name
        versions[name] = versions.get(name, 0) + 1
        return Value(version=versions[name], originator_id=name,
                     value=to_wire(db)).with_hash()

    def idle() -> bool:
        return not dec.debounce._task or dec.debounce._task.done()

    async def until(pred, what):
        deadline = time.monotonic() + 120
        while not pred():
            assert dec.last_rebuild_error is None, dec.last_rebuild_error
            assert time.monotonic() < deadline, f"timed out waiting for {what}"
            await asyncio.sleep(0.002)

    async def event(label: str, dbs: list) -> tuple[str, int, bool]:
        converted = counters.get(COUNTER)
        runs = counters.get("decision.spf_runs")
        pubs.push(Publication(
            area=AREA,
            key_vals={C.adj_key(db.this_node_name): value(db) for db in dbs},
        ))
        await until(lambda: counters.get("decision.spf_runs") > runs,
                    "the rebuild")
        await until(idle, "the rebuild coroutine's end")
        ls, = dec.link_states.values()
        ps, = dec.prefix_states.values()
        oracle = oracle_compute_routes(ls, ps, me, vectorize=False)
        same = (dec.rib.unicast_routes == oracle.unicast_routes
                and dec.rib.mpls_routes == oracle.mpls_routes)
        return label, counters.get(COUNTER) - converted, same

    await dec.start()
    await fib.start()
    try:
        for db, pdb in zip(adj_dbs, prefix_dbs):
            name = db.this_node_name
            kv = {C.adj_key(name): value(db)}
            for entry in pdb.prefix_entries:
                kv[C.prefix_key(name, AREA, str(entry.prefix))] = Value(
                    version=1, originator_id=name,
                    value=to_wire(pdb)).with_hash()
            pubs.push(Publication(area=AREA, key_vals=kv))
        synced.set()
        await until(lambda: dec.rib_computed.is_set() and fib.synced.is_set(),
                    "the first RIB")
        await until(idle, "the rebuild coroutine's end")
        assert counters.get(COUNTER) == len(adj_dbs)
        tor, agg = tor_uplink(g)
        pair = [(tor, agg), (agg, tor)]
        out = [await event("link down", [
            without(adj_dbs[u], topo.node_name(w)) for u, w in pair])]
        out.append(await event("link up", [adj_dbs[u] for u, _ in pair]))
        first = adj_dbs[agg].adjacencies[0]
        out.append(await event("metric", [dataclasses.replace(
            adj_dbs[agg], adjacencies=(
                dataclasses.replace(first, metric=first.metric + 3),
                *adj_dbs[agg].adjacencies[1:]))]))
        drained = topo.fat_tree_agg(g, 2, 1)
        out.append(await event("drain", [dataclasses.replace(
            adj_dbs[drained], is_overloaded=True)]))
        out.append(await event("undrain", [adj_dbs[drained]]))
        return out
    finally:
        await fib.stop()
        await dec.stop()
        for q in (pubs, routes, perf_events):
            q.close()


def test_decision_converts_two_databases_a_link_event_and_none_else(
        monkeypatch):
    monkeypatch.setattr(EdgeDetails, "__iter__", _raise)
    monkeypatch.setattr(EdgeIndex, "__iter__", _raise)
    got = asyncio.run(decision_story())
    assert [(label, n) for label, n, _ in got] == [
        ("link down", 2), ("link up", 2), ("metric", 0), ("drain", 0),
        ("undrain", 0)]
    assert all(same for *_, same in got), got


# ------------------------------------------------------------ hand-built


def test_a_hand_built_graph_with_a_plain_dict_solves_as_the_reference():
    g = topo.erdos_renyi(96, 4, 16, 3)
    root = 5
    ls, ps, csr = program_lsdb(g, root)
    assert type(csr.adj_details) is dict and type(csr.edge_index) is dict
    want_nbrs = sorted(d for (s, d) in csr.adj_details if s == root)
    assert csr.neighbor_ids(root) == want_nbrs
    assert csr.neighbor_ids(root + 1) == []  # details of the root alone
    rdb = TpuSpfSolver(native_rib="off").compute_routes(
        ls, ps, topo.node_name(root))
    want_u, want_m = reference.tables(g, root)
    got_u = compare.plain_unicast(
        [e.to_unicast_route() for e in rdb.unicast_routes.values()])
    got_m = compare.plain_mpls(
        [e.to_mpls_route() for e in rdb.mpls_routes.values()])
    assert compare.count_differences(got_u, want_u) == (0, [])
    assert compare.count_differences(got_m, want_m) == (0, [])
    assert len(want_u) == g.n - 1
