"""The SR-MPLS backbone's path at CPU size (configuration `backbone_ksp`,
PR 32): publication -> Decision(solver="tpu") in two areas -> warm start
-> the area's KSP batch -> cross-area fold -> Fib -> handler, over the
benchmark's own generator at 2 areas x 4 sites x 4 routers, held to the
plain reference (`perfbench/references/backbone_ksp.py`, which imports
nothing of the program), and what the deployment added to the program:

  * the spans `spf:ksp_solve`, `spf:ksp_fetch`, `spf:ksp_decode` inside
    `spf:ksp`, and the counters `decision.spf.ksp_jobs`, `.ksp_chunks`,
    `.ksp_rounds`, and (PR 33) `.ksp_path_nodes`: the decode reads the
    hops the kernel found, not the padded width of `paths`;
  * `costs`, `paths` and `hops` fetched through the counted transfer seam;
  * the pre-warm of the dense tables' scatter and of the KSP kernel.

One story of a dozen circuit cost-outs and restores, then four ring-link
events that move `abr-1` between the areas, is run twice (module fixture),
with the pre-warm and with it stubbed out; the cases read it.

`decision.rebuild.area_solves` counts full solves only and stays flat over
a warm start (docs/Monitor.md; tests/test_spf_delta.py holds it there), so
"the event's area was solved again" reads `decision.spf.warm_starts` +1
here, and `area_solves` +0, where ISSUE 32 wrote `area_solves` +1.
"""

import asyncio
import dataclasses
import sys
import time
from pathlib import Path

import jax
import pytest

from openr_tpu.common import constants as C
from openr_tpu.config import AreaConfig, Config
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.fib import Fib, MockFibHandler
from openr_tpu.fib.fib import CLIENT_ID_OPENR
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters, compile_ledger, names, perf
from openr_tpu.ops import ksp as ksp_ops
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.serde import to_wire
from perfbench import compare, topo
from perfbench.drivers.decision_fib_areas import program_dbs
from perfbench.events import link_pool
from perfbench.references import backbone_ksp as reference
from perfbench.topologies import backbone_sites

sys.path.insert(0, str(Path(__file__).resolve().parent / "perfbench"))
from perfbench_util import tiny_checkout  # noqa: E402

ROOT, ABR1 = 0, 1
PREFIXES = {"forwarding_algorithm": "KSP2_ED_ECMP", "forwarding_type": "SR_MPLS"}
OUT = 10000
CELL = "tiny_backbone.circuit_cost_out"


def tiny_graph() -> topo.Graph:
    return backbone_sites.build(
        areas=2, sites=4, routers=4, express=2, ring_metric=10, graph_seed=0)


def the_story(g: topo.Graph) -> list[tuple[tuple[int, int], int, str]]:
    """(link, metric, kind): six circuits, three an area, costed out and
    restored one after the other; then `abr-1`'s two ring links of area
    "1" (routers 1 and 3 of site 0) out, the second restored, the first
    restored: area "2" nearer, then a tie again."""
    own = g.meta["own_metric"]
    area_of = g.meta["edge_area"]
    by_area: dict[int, list] = {0: [], 1: []}
    for a, b in link_pool(g, "circuits_off_root", ROOT).tolist():
        by_area[int(area_of[g.edge_slot(a, b)])].append((a, b))
    circuits = [ln for pair in zip(by_area[0][:3], by_area[1][:3]) for ln in pair]
    events = []
    for ln in circuits:
        events.append((ln, OUT, "circuit_out"))
    for ln in circuits:
        events.append((ln, int(own[g.edge_slot(*ln)]), "circuit_restore"))
    names_ = g.meta["names"]
    r1, r3 = names_.index("a1-s0-r1"), names_.index("a1-s0-r3")
    events += [
        ((r1, ABR1), OUT, "ring_one_out"), ((r3, ABR1), OUT, "ring_both_out"),
        ((r3, ABR1), 10, "ring_one_back"), ((r1, ABR1), 10, "ring_both_back"),
    ]
    return events


async def run_story() -> dict:
    """First RIB, then the story, one event in flight; what each event
    did."""
    g = tiny_graph()
    events = the_story(g)
    names_, areas = g.meta["names"], g.meta["areas"]
    adj_dbs, prefix_dbs = program_dbs(g, PREFIXES)
    cfg = Config.default(names_[ROOT])
    cfg.node.areas = tuple(AreaConfig(area_id=a) for a in areas)
    cfg.node.decision.native_rib = "off"
    cfg.node.decision.ksp_paths = 16
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
    versions = dict.fromkeys(adj_dbs, 1)
    led = compile_ledger.ledger()

    def value(key):
        db = adj_dbs[key]
        return Value(version=versions[key], originator_id=db.this_node_name,
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

    def stat(name):
        return dec._tpu.spf_kernel_stats[name]

    # the nodes of the paths the decode handed to the route builder
    decoded = {"nodes": 0}
    real_paths_to_host = ksp_ops.paths_to_host

    def counting_paths_to_host(*args):
        host_paths = real_paths_to_host(*args)
        decoded["nodes"] += sum(len(path) for _cost, path in host_paths)
        return host_paths

    out: dict = {"events": [], "graph": g}
    jax.clear_caches()  # so that every program this story needs compiles in it
    await dec.start()
    await fib.start()
    patch = pytest.MonkeyPatch()
    patch.setattr(ksp_ops, "paths_to_host", counting_paths_to_host)
    try:
        for key, db in adj_dbs.items():
            area, name = areas[key[0]], db.this_node_name
            kv = {C.adj_key(name): value(key)}
            for entry in prefix_dbs[key].prefix_entries:
                kv[C.prefix_key(name, area, str(entry.prefix))] = Value(
                    version=1, originator_id=name,
                    value=to_wire(prefix_dbs[key])).with_hash()
            pubs.push(Publication(area=area, key_vals=kv))
        synced.set()
        await until(lambda: dec.rib_computed.is_set() and fib.synced.is_set(),
                    "the first RIB")
        await until(lambda: not dec.debounce._task or dec.debounce._task.done(),
                    "the rebuild coroutine's end")
        out["first_tables"] = await tables()
        out["first_want"] = reference.tables(g, ROOT)
        out["first_breakdown"] = dict(dec.last_breakdown_ms)
        out["prewarm_programs"] = counters.get("decision.spf.prewarm_programs")
        out["first_jobs"] = stat("ksp_jobs")
        out["first_path_nodes"] = (stat("ksp_path_nodes"), decoded["nodes"])
        for (a, b), metric, kind in events:
            area = int(g.meta["edge_area"][g.edge_slot(a, b)])
            g.set_metric(a, b, metric)
            changed = []
            for u, v in ((a, b), (b, a)):
                db = adj_dbs[(area, u)]
                adj_dbs[(area, u)] = dataclasses.replace(db, adjacencies=tuple(
                    dataclasses.replace(x, metric=metric)
                    if x.other_node_name == names_[v] else x
                    for x in db.adjacencies))
                versions[(area, u)] += 1
                changed.append((area, u))
            before = {n: counters.get(n) for n in (
                "decision.spf_runs", "decision.rebuild.area_solves",
                "decision.rebuild.cached_areas", "decision.spf.warm_starts",
                "decision.rebuild.topo_delta")}
            jobs0, chunks0, rounds0 = (
                stat("ksp_jobs"), stat("ksp_chunks"), stat("ksp_rounds"))
            nodes0, decoded0 = stat("ksp_path_nodes"), decoded["nodes"]
            bytes0, reads0 = led.host_bytes, led.host_reads
            mark = led.snapshot()
            pubs.push(Publication(
                area=areas[area],
                key_vals={C.adj_key(adj_dbs[k].this_node_name): value(k)
                          for k in changed},
                perf_events=perf.PerfEvents.start(
                    perf.KVSTORE_FLOODED, node="test"),
            ))
            await until(
                lambda: counters.get("decision.spf_runs") > before["decision.spf_runs"],
                "the rebuild")
            trace = await asyncio.wait_for(traces.get(), 60)
            assert trace.last_event() == perf.FIB_PROGRAMMED
            got = await tables()
            want = reference.tables(g, ROOT)
            out["events"].append({
                "link": (a, b), "metric": metric, "kind": kind, "area": area,
                "tables_right": got == want,
                "abr1": (got[0][topo.loopback(ABR1)],
                         got[1][topo.node_label(ABR1)]),
                "grew": {n: counters.get(n) - v for n, v in before.items()},
                "ksp_jobs": stat("ksp_jobs") - jobs0,
                "ksp_chunks": stat("ksp_chunks") - chunks0,
                "ksp_rounds": stat("ksp_rounds") - rounds0,
                "ksp_path_nodes": stat("ksp_path_nodes") - nodes0,
                "decoded_nodes": decoded["nodes"] - decoded0,
                "fetched": led.host_bytes - bytes0,
                "reads": led.host_reads - reads0,
                "compiled": mark.delta(led.snapshot()),
                "breakdown": dict(dec.last_breakdown_ms),
            })
        out["counters"] = dict(counters.counters)
        out["vp"] = {
            ls.area: ls.to_csr().padded_nodes
            for ls in dec.link_states.values()
        }
    finally:
        patch.undo()
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


KINDS = ("circuit_out", "circuit_restore", "ring_one_out", "ring_both_out",
         "ring_one_back", "ring_both_back")


def test_the_first_rib_equals_the_reference_in_both_areas(stories):
    story, _ = stories
    assert story["first_tables"] == story["first_want"]
    uni, mpls = story["first_want"]
    g = story["graph"]
    assert len(uni) == len(mpls) == g.n - 1  # every loopback, every label
    # every route a KSP route: a stack wherever the path has a second hop
    assert any(nh[6] == "PUSH" and len(nh[8]) > 2 for nhs in uni.values() for nh in nhs)
    assert {nh[5] for nhs in uni.values() for nh in nhs} == {"1", "2"}
    assert story["first_jobs"] == 2 * 15  # both areas' batches, cold


# (a)
@pytest.mark.parametrize("kind", KINDS)
def test_after_every_event_the_fib_equals_the_references_tables(stories, kind):
    events = [e for e in stories[0]["events"] if e["kind"] == kind]
    assert events
    assert sum(e["kind"].startswith("circuit") for e in stories[0]["events"]) == 12
    for e in events:
        assert e["tables_right"], e["link"]


# (b)
def test_an_event_solves_its_own_area_again_and_leaves_the_other_cached(stories):
    for e in stories[0]["events"]:
        grew = e["grew"]
        assert grew["decision.spf_runs"] == 1, e
        assert grew["decision.spf.warm_starts"] == 1, e
        assert grew["decision.rebuild.topo_delta"] == 1, e
        assert grew["decision.rebuild.cached_areas"] == 1, e
        assert grew["decision.rebuild.area_solves"] == 0, e  # full solves only
        # the area's whole KSP batch: its 16 routers less the root, one
        # chunk, dispatched with the 4 rounds the root's degree bounds
        assert (e["ksp_jobs"], e["ksp_chunks"], e["ksp_rounds"]) == (15, 1, 4), e
    assert {e["area"] for e in stories[0]["events"]} == {0, 1}


# (c)
def test_abr1_follows_the_fold_across_areas(stories):
    by_kind = {e["kind"]: e for e in stories[0]["events"]}

    def areas_of(e):
        loopback, label = e["abr1"]
        return {nh[5] for nh in loopback}, {nh[5] for nh in label}

    # one ring link of area "1" out: its other ring path still costs 20,
    # as area "2"'s do: a tie, the union
    assert areas_of(by_kind["ring_one_out"]) == ({"1", "2"}, {"1", "2"})
    # both out: area "1" reaches abr-1 over circuits only, area "2" is
    # nearer and takes the loopback (_fold_unicast) and the label (_fold_mpls)
    assert areas_of(by_kind["ring_both_out"]) == ({"2"}, {"2"})
    loopback, label = by_kind["ring_both_out"]["abr1"]
    assert min(nh[3] for nh in loopback) == 20 == min(nh[3] for nh in label)
    assert areas_of(by_kind["ring_one_back"]) == ({"1", "2"}, {"1", "2"})
    loopback, label = by_kind["ring_both_back"]["abr1"]
    assert len(loopback) == 8 and len(label) == 4  # 4 paths an area; 2 ECMP an area
    assert all(e["tables_right"] for e in by_kind.values())


# (d)
def test_after_the_prewarm_a_metric_event_compiles_nothing(stories):
    story, stubbed = stories
    # two areas of the same shapes: the split tables' scatter, the cone's,
    # the warm kernel, and for KSP the dense tables' scatter and the kernel
    # at the one batch size of the area's batch
    assert story["prewarm_programs"] == 5
    assert story["first_breakdown"]["spf:prewarm"] > 0.0
    for e in story["events"]:
        assert e["compiled"] == {}, e
    assert stubbed["prewarm_programs"] == 0
    first = stubbed["events"][0]["compiled"]
    assert "batched_sssp_split_warm_rib" in first and "_scatter_set" in first
    # the dense tables' scatter is a program of its own (another shape
    # than the split tables'), which the stubbed run meets in its first event
    assert first["_scatter_set"] >= 2


# (e)
def test_costs_and_paths_are_fetched_through_the_counted_seam(stories):
    story, _ = stories
    assert set(story["vp"].values()) == {32}
    for e in story["events"]:
        # k_eff x B x Vp int32 of paths, k_eff x B of costs and as much
        # of hops, a chunk
        ksp_bytes = e["ksp_rounds"] * 16 * 32 * 4 + 2 * e["ksp_rounds"] * 16 * 4
        assert e["fetched"] >= ksp_bytes, e
        assert e["reads"] >= 3 * e["ksp_chunks"] + 2  # + mirror + packed


def test_the_decode_reads_the_path_nodes_the_kernel_found(stories):
    """`decision.spf.ksp_path_nodes` (hops + 1 over the paths found) is
    what `paths_to_host` handed on, node for node, and a small part of
    the slots fetched."""
    story, _ = stories
    counted, decoded = story["first_path_nodes"]
    assert counted == decoded > 0  # both areas' cold batches
    for e in story["events"]:
        assert e["ksp_path_nodes"] == e["decoded_nodes"] > 0, e
        # 15 jobs of at least one path of at least two nodes; the kernel
        # filled k_eff x 16 x 32 slots
        assert 2 * e["ksp_jobs"] <= e["ksp_path_nodes"] < e["ksp_rounds"] * 16 * 32
    assert story["counters"]["decision.spf.ksp_path_nodes"] == sum(
        e["ksp_path_nodes"] for e in story["events"]) + counted


def test_the_ksp_spans_account_for_the_batch(stories):
    assert {"spf:ksp", "spf:ksp_solve", "spf:ksp_fetch", "spf:ksp_decode"} < set(
        names.REBUILD_SPANS)
    for e in stories[0]["events"]:
        bd = e["breakdown"]
        parts = bd["spf:ksp_solve"] + bd["spf:ksp_fetch"] + bd["spf:ksp_decode"]
        assert min(bd["spf:ksp_solve"], bd["spf:ksp_fetch"], bd["spf:ksp_decode"]) > 0
        # inside it and no longer than it; that they account for it to
        # within 5% is a chip reading (PERF.md): at this size a thread
        # switch between two chunks' spans is a third of the span
        assert parts <= bd["spf:ksp"] <= bd["spf:warm_reassemble"]


# (f) ------------------------------------------------------------------ faults


def drop_the_second_path(monkeypatch):
    """Every route with two or more next hops is programmed without its
    second."""
    real = MockFibHandler.add_unicast_routes

    async def dropped(self, client_id, routes):
        await real(self, client_id, [
            dataclasses.replace(r, nexthops=(r.nexthops[0], *r.nexthops[2:]))
            if len(r.nexthops) > 1 else r for r in routes])

    monkeypatch.setattr(MockFibHandler, "add_unicast_routes", dropped)


def truncate_the_label_stacks(monkeypatch):
    """Every stack of two or more labels loses the last it pushes."""
    real = MockFibHandler.add_unicast_routes

    def cut(nh):
        act = nh.mpls_action
        if act is None or len(act.push_labels) < 2:
            return nh
        return dataclasses.replace(nh, mpls_action=dataclasses.replace(
            act, push_labels=act.push_labels[:-1]))

    async def truncated(self, client_id, routes):
        await real(self, client_id, [
            dataclasses.replace(r, nexthops=tuple(map(cut, r.nexthops)))
            for r in routes])

    monkeypatch.setattr(MockFibHandler, "add_unicast_routes", truncated)


def leave_the_area_stale(monkeypatch):
    """A warm start hands back the area's cached routes unsolved: after an
    event the area is as it was before it."""

    def stale(self, art, ls, ps, my_node, edge_pairs, prefix_dirt, cached_rdb,
              max_frac):
        return cached_rdb, art, set(), set(), 0

    monkeypatch.setattr(TpuSpfSolver, "warm_compute_routes", stale)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("backbone"))


@pytest.mark.parametrize("fault", [
    drop_the_second_path, truncate_the_label_stacks, leave_the_area_stale])
def test_a_planted_fault_reads_not_correct(checkout, monkeypatch, fault):
    from perfbench import run

    fault(monkeypatch)
    rc, line = run.run_cell(checkout, CELL, 11, 1.0, False, time.perf_counter())
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["unicast_routes_differ"]["value"] > 0


def test_the_reference_imports_nothing_of_the_program():
    for module in (reference, backbone_sites):
        text = Path(module.__file__).read_text()
        assert "openr_tpu" not in text.split('"""', 2)[2], module.__name__


def test_the_control_breaks_edge_disjointness_and_nothing_else():
    g = tiny_graph()
    want_u, want_m = reference.tables(g, ROOT)
    ctl_u, ctl_m = reference.tables(g, ROOT, control=True)
    assert ctl_m == want_m
    assert sum(ctl_u[k] != want_u[k] for k in want_u) > len(want_u) // 3
    assert "edge-disjointness" in reference.CONTROL
