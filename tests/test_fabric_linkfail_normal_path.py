"""Links down and up through the normal path at CPU size (configuration
`fabric_linkfail`): publication -> Decision(solver="tpu") -> structural
rebuild on a changed adjacency set -> Fib -> handler, on k = 4 and k = 8
fabrics with a standing set of failed links, held after every event to the
plain reference (`perfbench/references/fabric_linkfail.py`, which imports
nothing of the program) and to the scalar oracle.

One story a fabric (module fixture): the first RIB with the standing set
down; a seeded sequence of downs and ups (`decision_fib_linkfail`'s order:
down, down, up the oldest, ...); a link down at one end only, then at the
other, then back at both; a link down and straight back up.
"""

import asyncio
import dataclasses
import time

import numpy as np
import pytest

from openr_tpu.common import constants as C
from openr_tpu.config import Config
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.fib import Fib, MockFibHandler
from openr_tpu.fib.fib import CLIENT_ID_OPENR
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters, perf
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.serde import to_wire
from perfbench import compare, topo
from perfbench.drivers.decision_fib import AREA, program_dbs
from perfbench.drivers.decision_fib_linkfail import link_sequence
from perfbench.references import fabric_linkfail as reference
from perfbench.topologies import fat_tree_failed

#: k -> (standing ToR<->agg, standing agg<->spine, traffic seed)
FABRICS = {4: (1, 1, 2147483659), 8: (3, 3, 5)}
#: events of the seeded sequence a story
SEQUENCE = 14
COUNTED = ("decision.rebuild.structural", "decision.csr.builds",
           "decision.csr.overload_rebases", "decision.csr.adjacencies_walked",
           "decision.spf.warm_starts", "decision.spf.engine_native",
           "decision.csr.interning_kept", "decision.spf.assembly_carried")


def link(u: int, v: int) -> tuple[int, int]:
    return min(u, v), max(u, v)


async def run_story(k: int) -> dict:
    tor_agg, agg_spine, seed = FABRICS[k]
    g = fat_tree_failed.build(k, tor_agg, agg_spine, 0)
    root = topo.fat_tree_tor(g, 0, 0)
    me = topo.node_name(root)
    adj_dbs, prefix_dbs = program_dbs(g)
    whole = [db.adjacencies for db in adj_dbs]
    nbrs: list[list[int]] = [[] for _ in range(g.n)]
    for u, v in zip(g.src.tolist(), g.dst.tolist()):
        nbrs[u].append(v)
    down = set(g.meta["failed"])

    def published(node: int, gone=()) -> object:
        """`node`'s database without its links that are down, and without
        the links in `gone` (a withdrawal at this end alone)."""
        out = set(down) | set(gone)
        return dataclasses.replace(adj_dbs[node], adjacencies=tuple(
            a for a, v in zip(whole[node], nbrs[node])
            if link(node, v) not in out))

    for node in {s for ln in down for s in ln}:
        adj_dbs[node] = published(node)

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
        return {key: counters.get(key) or 0 for key in COUNTED}

    async def step(label: str, before: dict) -> dict:
        after = counted()
        ls, = dec.link_states.values()
        ps, = dec.prefix_states.values()
        oracle = oracle_compute_routes(ls, ps, me, vectorize=False)
        return {
            "label": label,
            "down": frozenset(down),
            "grew": {key: after[key] - before[key] for key in COUNTED},
            "lsdb_adjacencies": sum(
                len(ls.adjacency_db(n).adjacencies) for n in ls.nodes),
            "breakdown": dict(dec.last_breakdown_ms),
            "tables": (
                compare.plain_unicast(
                    await handler.get_route_table_by_client(CLIENT_ID_OPENR)),
                compare.plain_mpls(
                    await handler.get_mpls_route_table_by_client(CLIENT_ID_OPENR)),
            ),
            "graph": fat_tree_failed.as_published(g, down),
            "rib_is_the_oracles": (
                dec.rib.unicast_routes == oracle.unicast_routes
                and dec.rib.mpls_routes == oracle.mpls_routes),
        }

    async def event(label: str, dbs: list, reaches_fib: bool = True) -> dict:
        before = counted()
        runs = counters.get("decision.spf_runs")
        for db in dbs:
            adj_dbs[topo_id(db)] = db
        pubs.push(Publication(
            area=AREA,
            key_vals={C.adj_key(db.this_node_name): value(db) for db in dbs},
            perf_events=perf.PerfEvents.start(perf.KVSTORE_FLOODED, node="test"),
        ))
        await until(lambda: counters.get("decision.spf_runs") > runs, "the rebuild")
        if reaches_fib:
            trace = await asyncio.wait_for(traces.get(), 60)
            assert trace.last_event() == perf.FIB_PROGRAMMED
        await until(lambda: not dec.debounce._task or dec.debounce._task.done(),
                    "the rebuild coroutine's end")
        if not reaches_fib:
            await asyncio.sleep(0.2)
            assert traces.size() == 0, "no route changed: Fib is told nothing"
        return await step(label, before)

    def topo_id(db) -> int:
        return int(db.this_node_name.rpartition("-")[2])

    def both_ends(ln: tuple[int, int], is_down: bool) -> list:
        (down.add if is_down else down.remove)(link(*ln))
        return [published(n) for n in ln]

    out: dict = {"g": g, "root": root, "steps": []}
    await dec.start()
    await fib.start()
    try:
        before = counted()
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
        out["cold"] = await step("cold", before)
        sequence = link_sequence(g, np.random.default_rng(seed), {"max_down": 2})
        for i in range(SEQUENCE):
            ln, is_down = next(sequence)
            s = await event(f"{'down' if is_down else 'up'} {ln}",
                            both_ends(ln, is_down))
            s["link"], s["is_down"] = ln, is_down
            out["steps"].append(s)
        # one end withdraws the adjacency, the other still advertises it;
        # then the other end too; then both put it back
        held = {s for ln in down for s in ln}
        pool = [tuple(r) for r in g.meta["link_pools"]["tor_agg_up"].tolist()
                if not set(r) & held]
        agg, tor = pool[0]
        out["one_sided_link"] = (agg, tor)
        out["before_one_sided"] = await step("before one-sided", counted())
        one = published(tor, gone=[link(agg, tor)])
        out["one_end"] = await event("down at the ToR alone", [one])
        down.add(link(agg, tor))
        out["other_end"] = await event(
            "down at the agg too", [published(agg)], reaches_fib=False)
        out["back_up"] = await event("up at both ends",
                                     both_ends((agg, tor), False))
        # a link down and straight back up
        agg, tor = pool[1]
        out["before_flap"] = await step("before the flap", counted())
        out["flap_down"] = await event("flap down", both_ends((agg, tor), True))
        out["flap_up"] = await event("flap up", both_ends((agg, tor), False))
    finally:
        await fib.stop()
        await dec.stop()
        for q in (pubs, routes, perf_events):
            q.close()
    return out


@pytest.fixture(scope="module", params=sorted(FABRICS), ids=lambda k: f"k{k}")
def story(request):
    return asyncio.run(run_story(request.param))


def every_step(story) -> list[dict]:
    """Every step whose graph is the LSDB as published (the one-sided
    withdrawal has a test of its own)."""
    return [story["cold"], *story["steps"], story["other_end"],
            story["back_up"], story["flap_down"], story["flap_up"]]


def test_after_every_event_the_fib_is_the_references(story):
    steps = every_step(story)
    assert len(story["steps"]) >= 12
    for s in steps:
        got_u, got_m = s["tables"]
        want_u, want_m = reference.tables(s["graph"], story["root"])
        assert compare.count_differences(got_u, want_u) == (0, []), s["label"]
        assert compare.count_differences(got_m, want_m) == (0, []), s["label"]
        assert s["rib_is_the_oracles"], s["label"]
        k = story["g"].meta["k"]
        assert len(want_u) == 5 * k * k // 4 - 1, "every switch stays reachable"


def test_the_sequence_holds_two_down_and_brings_the_oldest_back(story):
    kinds = [s["is_down"] for s in story["steps"]]
    assert kinds[:5] == [True, True, False, True, False]
    standing = story["g"].meta["failed"]
    for s in story["steps"]:
        assert standing <= s["down"] and len(s["down"] - standing) <= 2


def test_each_event_moves_one_tor_through_one_plane(story):
    """A ToR<->agg link down: the root's loopback and label route to that
    ToR lose the next hop through the agg's plane, and nothing else moves;
    up: they get it back."""
    g, prev = story["g"], story["cold"]
    for s in story["steps"]:
        agg, tor = s["link"]
        a = (agg - g.meta["n_core"]) % g.meta["half"]
        plane = topo.node_name(topo.fat_tree_agg(g, 0, a))
        got_u, got_m = s["tables"]
        was_u, was_m = prev["tables"]
        key = topo.loopback(tor)
        assert {k for k in got_u if got_u[k] != was_u[k]} == {key}, s["label"]
        assert {k for k in got_m if got_m[k] != was_m[k]} == {
            topo.node_label(tor)}, s["label"]
        lost = {nh[0] for nh in was_u[key]} - {nh[0] for nh in got_u[key]}
        won = {nh[0] for nh in got_u[key]} - {nh[0] for nh in was_u[key]}
        assert (lost, won) == (({plane}, set()) if s["is_down"]
                               else (set(), {plane})), s["label"]
        prev = s


def test_every_event_builds_the_csr_and_walks_the_whole_lsdb(story):
    """A changed adjacency set is a build: one structural rebuild, one
    `_build_csr` walk over every adjacency the LSDB holds, no re-base, no
    warm start, the device engine."""
    for s in [story["cold"], *story["steps"], story["one_end"],
              story["back_up"], story["flap_down"], story["flap_up"]]:
        grew = s["grew"]
        if s is not story["cold"]:
            assert grew["decision.rebuild.structural"] == 1, s["label"]
        assert grew["decision.csr.builds"] == 1, s["label"]
        assert grew["decision.csr.adjacencies_walked"] == s["lsdb_adjacencies"]
        assert grew["decision.csr.overload_rebases"] == 0, s["label"]
        assert grew["decision.spf.warm_starts"] == 0, s["label"]
        assert grew["decision.spf.engine_native"] == 0, s["label"]
        bd = s["breakdown"]
        parts = sum(bd[n] for n in ("spf:csr_index", "spf:csr_walk",
                                    "spf:csr_collapse", "spf:csr_sort",
                                    "spf:csr_masks"))
        assert 0 < parts <= bd["spf:to_csr"], s["label"]
    k = story["g"].meta["k"]
    full = k * k * k // 2 * 2  # k^3/4 links a tier pair, two tiers, both ways
    standing = len(story["g"].meta["failed"])
    assert story["cold"]["lsdb_adjacencies"] == full - 2 * standing
    for s in story["steps"]:
        assert s["lsdb_adjacencies"] == full - 2 * len(s["down"]), s["label"]


def test_every_event_keeps_the_interning_and_the_unicast_cell(story):
    """A link down or up leaves the node set as it was: each event's build
    keeps the last build's interning, and the cold assembly on the new
    base keeps the last RIB's unicast cell, one of each an event after the
    first RIB (the first RIB interns and fills the cell, and reads 0)."""
    assert story["cold"]["grew"]["decision.csr.interning_kept"] == 0
    assert story["cold"]["grew"]["decision.spf.assembly_carried"] == 0
    for s in [*story["steps"], story["one_end"], story["other_end"],
              story["back_up"], story["flap_down"], story["flap_up"]]:
        assert s["grew"]["decision.csr.interning_kept"] == 1, s["label"]
        assert s["grew"]["decision.spf.assembly_carried"] == 1, s["label"]


def test_a_link_down_at_one_end_only_is_dropped_both_ways(story):
    """The bidirectional check: the agg still advertises the adjacency,
    the ToR does not, and the link carries nothing in either direction:
    the FIB is the reference's with the link failed. The other end's
    withdrawal then changes no route (Fib is told nothing)."""
    agg, tor = story["one_sided_link"]
    g = story["g"]
    one = story["one_end"]
    assert link(agg, tor) not in one["down"]  # as the test's set has it
    want = reference.tables(
        fat_tree_failed.as_published(g, one["down"] | {link(agg, tor)}),
        story["root"])
    assert compare.count_differences(one["tables"][0], want[0]) == (0, [])
    assert compare.count_differences(one["tables"][1], want[1]) == (0, [])
    # and that is not the table with the link up
    was = story["before_one_sided"]["tables"]
    assert compare.count_differences(one["tables"][0], was[0])[0] == 1
    other = story["other_end"]
    assert other["tables"] == one["tables"]
    assert other["grew"]["decision.csr.builds"] == 1
    assert story["back_up"]["tables"] == was


def test_a_link_down_then_up_returns_the_fib_route_for_route(story):
    before, flap_down, flap_up = (
        story["before_flap"], story["flap_down"], story["flap_up"])
    assert flap_down["tables"] != before["tables"]
    assert flap_up["tables"] == before["tables"]
    assert flap_up["down"] == before["down"]


def test_the_control_keeps_the_failed_links_and_reads_not_correct(story):
    """The reference's control, the failed links kept in the graph, differs
    from the FIB by the standing ToR<->agg failures' ToRs, one loopback and
    one label route each; an agg<->spine failure moves no route here."""
    got_u, got_m = story["cold"]["tables"]
    ctl_u, ctl_m = reference.tables(story["cold"]["graph"], story["root"],
                                    control=True)
    tor_agg = FABRICS[story["g"].meta["k"]][0]
    assert compare.count_differences(got_u, ctl_u)[0] == tor_agg
    assert compare.count_differences(got_m, ctl_m)[0] == tor_agg
