"""Million-prefix data-plane tests: vectorized election byte-parity,
nexthop-group interning, delta-native FIB programming, range
origination.

The load-bearing contract: the batched election (decision/election.py)
+ grouped assembly must be BYTE-EQUAL to the
per-prefix scalar path (`oracle.compute_routes(vectorize=False)`) on
both engines, under randomized churn covering anycast ECMP ties,
drained links, node overloads, and the MPLS label tables — the
test_rebuild_scoped pattern extended to the election classes.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

from openr_tpu.common.constants import DEFAULT_AREA, adj_key, prefix_key
from openr_tpu.config import Config, NodeConfig
from openr_tpu.decision import election
from openr_tpu.decision.decision import Decision, merge_area_ribs
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.fib import Fib, MockFibHandler
from openr_tpu.fib.fib import CLIENT_ID_OPENR
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters
from openr_tpu.prefixmgr.ranges import PrefixRange
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.network import IpPrefix, NextHop
from openr_tpu.types.routes import (
    NexthopGroup,
    NexthopIntern,
    RibEntry,
    RouteUpdate,
    RouteUpdateType,
)
from openr_tpu.types.serde import from_wire, to_wire
from openr_tpu.types.topology import (
    ForwardingAlgorithm,
    PrefixDatabase,
    PrefixEntry,
    PrefixMetrics,
)
from openr_tpu.utils import topogen


def run(coro):
    return asyncio.run(coro)


def mk_decision(backend="cpu", name="node-0"):
    cfg = Config(NodeConfig(node_name=name))
    pubs = ReplicateQueue(name="pubs")
    routes = ReplicateQueue(name="routes")
    return Decision(
        cfg, pubs.get_reader(), routes, solver=backend, counters=Counters()
    )


def adj_pub(adj_dbs, area=DEFAULT_AREA, version=1):
    return Publication(
        area=area,
        key_vals={
            adj_key(db.this_node_name): Value(
                version=version,
                originator_id=db.this_node_name,
                value=to_wire(db),
            ).with_hash()
            for db in adj_dbs
        },
    )


def prefix_pub(node, entries, area=DEFAULT_AREA, version=1):
    kv = {}
    for e in entries:
        key = prefix_key(node, area, str(e.prefix.prefix))
        kv[key] = Value(
            version=version,
            originator_id=node,
            value=to_wire(
                PrefixDatabase(
                    this_node_name=node, prefix_entries=(e,), area=area
                )
            ),
        ).with_hash()
    return Publication(area=area, key_vals=kv)


def scalar_rib(d: Decision):
    """The per-prefix scalar reference RIB for a Decision's current
    LSDB — what every vectorized path is byte-parity-gated against."""
    states = d._snapshot_states()
    per_area = {
        a: oracle_compute_routes(ls, ps, d.node_name, vectorize=False)
        for a, (ls, ps) in states.items()
    }
    return merge_area_ribs(per_area, d.node_name)


def assert_scalar_parity(d: Decision, step=None):
    ref = scalar_rib(d)
    assert d.rib.unicast_routes == ref.unicast_routes, step
    assert d.rib.mpls_routes == ref.mpls_routes, step


def anycast_entry(pstr, pp=1000, sp=100, dist=0, **kw):
    return PrefixEntry(
        prefix=IpPrefix(prefix=pstr),
        metrics=PrefixMetrics(
            path_preference=pp, source_preference=sp, distance=dist
        ),
        **kw,
    )


# ------------------------------------------------------ election parity


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_randomized_election_churn_parity(backend):
    """After EVERY rebuild of a randomized churn sequence — anycast
    advertise/withdraw with preference splits and exact ECMP ties,
    metric flaps, link drains (adjacency overload), node overload
    toggles, plus KSP / UCMP / min_nexthop fallback prefixes — the
    published RIB (vectorized election + grouped assembly) equals the
    per-prefix scalar oracle, unicast AND MPLS, on both engines."""

    async def body():
        d = mk_decision(backend)
        adj_dbs, prefix_dbs = topogen.fat_tree(4)
        names = [db.this_node_name for db in adj_dbs]
        d.process_publication(adj_pub(adj_dbs))
        for db in prefix_dbs:
            d.process_publication(
                prefix_pub(db.this_node_name, db.prefix_entries)
            )
        # fallback-seam prefixes ride along the whole sequence
        d.process_publication(
            prefix_pub(
                names[2],
                (
                    anycast_entry("10.90.0.0/24", weight=4),  # UCMP
                    anycast_entry("10.91.0.0/24", min_nexthop=9),
                    dataclasses.replace(
                        anycast_entry("10.92.0.0/24"),
                        forwarding_algorithm=(
                            ForwardingAlgorithm.KSP2_ED_ECMP
                        ),
                    ),
                ),
            )
        )
        await d._rebuild_routes()
        assert_scalar_parity(d, "initial")

        rng = np.random.default_rng(7)
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        for step in range(16):
            op = int(rng.integers(0, 10))
            name = names[int(rng.integers(1, len(names)))]
            if op < 5:
                # anycast churn: 2-3 advertisers, tied or split keys
                k = int(rng.integers(0, 6))
                pstr = f"10.77.{k}.0/24"
                advs = rng.choice(
                    len(names), size=int(rng.integers(2, 4)), replace=False
                )
                tie = bool(rng.integers(0, 2))
                for j, a in enumerate(advs):
                    e = anycast_entry(
                        pstr,
                        pp=1000 if tie else 1000 + (j % 2),
                        dist=0 if tie else int(rng.integers(0, 2)),
                    )
                    d.process_publication(
                        prefix_pub(names[a], (e,), version=step + 2)
                    )
                if op == 4 and step > 4:
                    # withdraw one advertiser again
                    d.process_publication(
                        Publication(
                            expired_keys=[
                                prefix_key(
                                    names[advs[0]], DEFAULT_AREA, pstr
                                )
                            ]
                        )
                    )
            elif op < 7:
                # metric flap
                db = adj_cur[name]
                adjs = list(db.adjacencies)
                i = int(rng.integers(0, len(adjs)))
                adjs[i] = dataclasses.replace(
                    adjs[i], metric=int(rng.integers(1, 20))
                )
                db = dataclasses.replace(db, adjacencies=tuple(adjs))
                adj_cur[name] = db
                d.process_publication(adj_pub([db], version=step + 2))
            elif op < 8:
                # link drain: soft-overload one adjacency (both
                # directions drop — the drained-link election case)
                db = adj_cur[name]
                adjs = list(db.adjacencies)
                i = int(rng.integers(0, len(adjs)))
                adjs[i] = dataclasses.replace(
                    adjs[i], is_overloaded=not adjs[i].is_overloaded
                )
                db = dataclasses.replace(db, adjacencies=tuple(adjs))
                adj_cur[name] = db
                d.process_publication(adj_pub([db], version=step + 2))
            else:
                # node overload toggle (no-transit election masking)
                db = dataclasses.replace(
                    adj_cur[name],
                    is_overloaded=not adj_cur[name].is_overloaded,
                )
                adj_cur[name] = db
                d.process_publication(adj_pub([db], version=step + 2))
            await d._rebuild_routes()
            assert_scalar_parity(d, f"step {step}")
        # the sequence must actually have elected multi-advertiser
        # prefixes through the matrix (not the scalar fallback)
        if d._tpu is not None:
            assert d._tpu.elect_stats["multi"] > 0

    run(body())


def test_solver_elects_an_anycast_table_past_32k_slots_as_the_oracle():
    """An advertiser matrix of more than 32,768 slots, over twice the largest
    a benchmark cell holds, is elected by the solver's NumPy path to the
    scalar oracle's routes: preference splits, distance splits, ECMP
    ties among the best, and prefixes the root advertises itself."""
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    adj_dbs, _ = topogen.grid(3, 3)
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    names = [db.this_node_name for db in adj_dbs]
    rng = np.random.default_rng(5)
    per_node: dict[str, list] = {n: [] for n in names}
    n_prefixes = 4500
    for k in range(n_prefixes):
        pstr = f"10.{60 + k // 256}.{k % 256}.0/24"
        for a in rng.choice(len(names), int(rng.integers(6, 10)), False):
            per_node[names[a]].append(anycast_entry(
                pstr, pp=int(rng.choice((500, 1000))),
                dist=int(rng.integers(0, 2)),
            ))
    for n, entries in per_node.items():
        ps.update_prefix_db(
            PrefixDatabase(this_node_name=n, prefix_entries=tuple(entries))
        )
    csr = ls.to_csr()
    view = ps.election_view(csr.name_to_id, csr.structure_key)
    assert len(view.multi.adv) > 1 << 15
    assert len(view.multi.prefixes) == n_prefixes
    solver = TpuSpfSolver(native_rib="off")
    got = solver.compute_routes(ls, ps, "node-4")
    ref = oracle_compute_routes(ls, ps, "node-4", vectorize=False)
    assert solver.elect_stats["multi"] == n_prefixes
    assert 0 < len(ref.unicast_routes) < n_prefixes  # some local
    assert got.unicast_routes == ref.unicast_routes
    assert got.mpls_routes == ref.mpls_routes


def test_multi_sig_cache_sees_fh_change():
    """Regression (review finding): a remote metric raise that drops
    one of two equal-cost paths leaves d_root AND the election outcome
    byte-identical — the multi-section signature must still miss (it
    covers the advertisers' first-hop columns), or anycast routes would
    re-land with the dead first hop."""
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.types.topology import Adjacency, AdjacencyDatabase

    # A—B—X—D and A—C—Y—D (both cost 3 ⇒ fh {B, C}); E hangs off D
    links = [
        ("A", "B", 1), ("A", "C", 1), ("B", "X", 1), ("C", "Y", 1),
        ("X", "D", 1), ("Y", "D", 1), ("D", "E", 1),
    ]

    def dbs(metric_xd):
        per: dict[str, list] = {}
        for u, v, m in links:
            mm = metric_xd if {u, v} == {"X", "D"} else m
            per.setdefault(u, []).append(
                Adjacency(
                    other_node_name=v, if_name=f"if_{u}_{v}",
                    other_if_name=f"if_{v}_{u}", metric=mm,
                )
            )
            per.setdefault(v, []).append(
                Adjacency(
                    other_node_name=u, if_name=f"if_{v}_{u}",
                    other_if_name=f"if_{u}_{v}", metric=mm,
                )
            )
        return [
            AdjacencyDatabase(
                this_node_name=n, adjacencies=tuple(a), node_label=101 + i
            )
            for i, (n, a) in enumerate(sorted(per.items()))
        ]

    ls, ps = LinkState(), PrefixState()
    for db in dbs(1):
        ls.update_adjacency_db(db)
    p = anycast_entry("10.40.0.0/24")  # D wins (higher preference)
    ps.update_prefix_db(
        PrefixDatabase(this_node_name="D", prefix_entries=(p,))
    )
    ps.update_prefix_db(
        PrefixDatabase(
            this_node_name="E",
            prefix_entries=(anycast_entry("10.40.0.0/24", pp=500),),
        )
    )
    solver = TpuSpfSolver(native_rib="off")
    rdb1 = solver.compute_routes(ls, ps, "A")
    pref = IpPrefix(prefix="10.40.0.0/24")
    assert {n.neighbor_node for n in rdb1.unicast_routes[pref].nexthops} == {
        "B", "C"
    }
    # raise X→D to 2: via-B path now costs 4, via-C stays 3 — d(D) and
    # every election array unchanged, first hops shrink to {C}. The
    # CSR base is unchanged (metric-only patch), so the view gen and
    # assembly cache survive — exactly the stale-signature window.
    for db in dbs(2):
        ls.update_adjacency_db(db)
    rdb2 = solver.compute_routes(ls, ps, "A")
    ref = oracle_compute_routes(ls, ps, "A", vectorize=False)
    assert rdb2.unicast_routes == ref.unicast_routes
    assert {n.neighbor_node for n in rdb2.unicast_routes[pref].nexthops} == {
        "C"
    }


# -------------------------------------------------- nexthop-group intern


def test_nexthop_group_semantics():
    nh1 = NextHop(address="a", if_name="i1", metric=3, neighbor_node="a")
    nh2 = NextHop(address="b", if_name="i2", metric=3, neighbor_node="b")
    tab = NexthopIntern()
    g1 = tab.intern((nh1, nh2))
    g2 = tab.intern((nh1, nh2))
    assert g1 is g2  # interned identity
    assert tab.hits == 1 and len(tab) == 1
    assert isinstance(g1, tuple)  # transparent tuple subclass
    assert g1 == (nh1, nh2) and (nh1, nh2) == g1
    assert hash(g1) == hash((nh1, nh2))
    other = NexthopIntern().intern((nh1, nh2))
    assert g1 == other and g1 is not other  # cross-table: content eq
    assert g1 != (nh1,)
    # serde transparency: a group-bearing route encodes like a tuple
    e = RibEntry(prefix=IpPrefix(prefix="10.0.0.0/24"), nexthops=g1)
    r = e.to_unicast_route()
    decoded = from_wire(to_wire(r), type(r))
    assert decoded == r

    # RibEntry equality across group/tuple mixes (scalar vs vectorized)
    e2 = RibEntry(prefix=IpPrefix(prefix="10.0.0.0/24"), nexthops=(nh1, nh2))
    assert e == e2


def test_solver_assembly_shares_groups():
    """Two routes to the same originator class bind THE SAME group
    object, and a repeat rebuild reuses it (the diff's pointer-compare
    fuel)."""
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    adj_dbs, _ = topogen.ring(4)
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for k in range(4):
        ps.update_prefix_db(
            PrefixDatabase(
                this_node_name="node-2",
                prefix_entries=(anycast_entry(f"10.60.{k}.0/24"),),
            )
        )
    solver = TpuSpfSolver(native_rib="off")
    rdb = solver.compute_routes(ls, ps, "node-0")
    groups = {
        id(e.nexthops) for e in rdb.unicast_routes.values()
    }
    assert len(groups) == 1  # one shared NexthopGroup for the class
    e0 = next(iter(rdb.unicast_routes.values()))
    assert isinstance(e0.nexthops, NexthopGroup)
    rdb2 = solver.compute_routes(ls, ps, "node-0")
    e1 = next(iter(rdb2.unicast_routes.values()))
    assert e1.nexthops is e0.nexthops  # interned across rebuilds


# ------------------------------------------------------ delta-native FIB


def mk_fib(batch_size=None):
    cfg = Config(NodeConfig(node_name="node-0"))
    cfg.node.fib.initial_retry_ms = 4
    cfg.node.fib.max_retry_ms = 64
    if batch_size is not None:
        cfg.node.fib.program_batch_size = batch_size
    routes = ReplicateQueue(name="routes")
    handler = MockFibHandler()
    fib = Fib(
        cfg,
        routes.get_reader(),
        handler,
        fib_updates_queue=ReplicateQueue(name="fib_updates"),
        counters=Counters(),
    )
    return fib, handler


def rib_entry(pstr, *nbrs):
    return RibEntry(
        prefix=IpPrefix.make(pstr),
        nexthops=tuple(
            NextHop(address=n, if_name=f"if-{n}", metric=1, neighbor_node=n)
            for n in nbrs
        ),
    )


def test_fib_idle_cycle_is_o1():
    """After a big table lands, a program cycle with an empty delta
    book does NO handler ops, derives NO routes, scans NOTHING —
    counter-asserted (the satellite's O(prefixes)-copy fix)."""

    async def body():
        fib, handler = mk_fib()
        fib._have_rib = True
        entries = [
            rib_entry(f"10.{i >> 8}.{i & 0xFF}.0/24", "a") for i in range(512)
        ]
        fib._fold_update(
            RouteUpdate(
                type=RouteUpdateType.FULL_SYNC,
                unicast_to_update={e.prefix: e for e in entries},
            )
        )
        await fib._program_once()
        assert len(handler.unicast[CLIENT_ID_OPENR]) == 512
        ops0 = handler.op_count
        scans0 = fib.counters.get("fib.program_scan_routes") or 0
        # idle passes: dirty flag set with nothing pending
        for _ in range(3):
            await fib._program_once()
        assert handler.op_count == ops0
        assert (fib.counters.get("fib.program_scan_routes") or 0) == scans0
        # a 1-route delta scans exactly 1 and programs exactly 1
        e = rib_entry("10.99.0.0/24", "b")
        fib._fold_update(RouteUpdate(unicast_to_update={e.prefix: e}))
        await fib._program_once()
        assert handler.op_count == ops0 + 1
        assert (
            fib.counters.get("fib.program_scan_routes") or 0
        ) == scans0 + 1
        assert e.prefix in handler.unicast[CLIENT_ID_OPENR]

    run(body())


def test_fib_delta_batching():
    """A wide delta ships in program_batch_size chunks; deletes of
    never-programmed prefixes are skipped; identical rebindings are
    no-ops."""

    async def body():
        fib, handler = mk_fib(batch_size=8)
        fib._have_rib = True
        fib._need_full_sync = False  # jump straight to the delta path
        ents = {
            (e := rib_entry(f"10.1.{i}.0/24", "a")).prefix: e
            for i in range(20)
        }
        fib._fold_update(RouteUpdate(unicast_to_update=dict(ents)))
        await fib._program_once()
        assert len(handler.unicast[CLIENT_ID_OPENR]) == 20
        assert handler.op_count == 3  # ceil(20 / 8) chunked add calls
        assert (fib.counters.get("fib.program_batches") or 0) == 3
        assert (fib.counters.get("fib.routes_programmed") or 0) == 20
        ops0 = handler.op_count
        # identical rebinding (same UnicastRoute projection): no-op
        fib._fold_update(
            RouteUpdate(
                unicast_to_update={p: e for p, e in list(ents.items())[:5]}
            )
        )
        # plus a delete of something never programmed
        fib._fold_update(
            RouteUpdate(unicast_to_delete=[IpPrefix.make("10.250.0.0/24")])
        )
        await fib._program_once()
        assert handler.op_count == ops0

    run(body())


def test_fib_failure_mid_delta_full_resyncs():
    """A failing chunk re-enters SYNCING: the retry path re-derives the
    whole table via sync_fib and converges (nothing lost from the
    popped delta book)."""

    async def body():
        fib, handler = mk_fib()
        await fib.start()
        routes = fib.reader  # not used directly; drive via fold
        assert routes is not None
        e1 = rib_entry("10.0.1.0/24", "a")
        fib._fold_update(
            RouteUpdate(
                type=RouteUpdateType.FULL_SYNC,
                unicast_to_update={e1.prefix: e1},
            )
        )
        fib._have_rib = True
        fib._dirty.set()
        t0 = asyncio.get_event_loop().time()
        while not fib.synced.is_set():
            await asyncio.sleep(0.005)
            assert asyncio.get_event_loop().time() - t0 < 5
        syncs0 = handler.sync_count
        handler.fail_next_n = 1
        e2 = rib_entry("10.0.2.0/24", "b")
        fib._fold_update(RouteUpdate(unicast_to_update={e2.prefix: e2}))
        fib._dirty.set()
        t0 = asyncio.get_event_loop().time()
        while e2.prefix not in handler.unicast.get(CLIENT_ID_OPENR, {}):
            await asyncio.sleep(0.005)
            assert asyncio.get_event_loop().time() - t0 < 5
        assert handler.sync_count > syncs0  # recovered via full resync
        assert fib.pending_changes()["converged"]
        await fib.stop()

    run(body())


# ------------------------------------------------------ range origination


def test_prefix_range_arithmetic():
    r = PrefixRange(base="16.0.0.0", plen=32, count=300)
    assert len(r) == 300
    assert str(r.prefix_at(0)) == "16.0.0.0/32"
    assert str(r.prefix_at(299)) == "16.0.1.43/32"
    with pytest.raises(IndexError):
        r.prefix_at(300)
    with pytest.raises(ValueError):
        PrefixRange(base="16.0.0.1", plen=24, count=2)  # misaligned
    r24 = PrefixRange(base="10.128.0.0", plen=24, count=4)
    assert [str(p) for p in (r24.prefix_at(i) for i in range(4))] == [
        "10.128.0.0/24",
        "10.128.1.0/24",
        "10.128.2.0/24",
        "10.128.3.0/24",
    ]
    # chunks are lazy and cover the range exactly once
    got = [e.prefix for _f, es in r.chunks(128) for e in es]
    assert got == [r.prefix_at(i) for i in range(300)]
    # canonical strings: IpPrefix.make agrees
    assert IpPrefix.make(str(r.prefix_at(77).prefix)) == r.prefix_at(77)


def test_prefix_manager_range_origination():
    """A 2.5k-prefix range advertises as ceil(2500/1024)=3 chunked
    per-prefix keys (not 2500), withdraws with tombstones, and a
    Decision fed those values learns every member prefix."""
    from openr_tpu.prefixmgr.prefix_manager import (
        PrefixEvent,
        PrefixEventType,
        PrefixManager,
        PrefixSource,
    )

    class StubKv:
        def __init__(self):
            self.persisted = []
            self.unset = []

        def persist_key(self, area, key, value, ttl_ms=None):
            self.persisted.append((area, key, value))

        def unset_key(self, area, key):
            self.unset.append((area, key))

    cfg = Config(NodeConfig(node_name="node-0"))
    kv = StubKv()
    pm = PrefixManager(cfg, kv, counters=Counters())
    rng = PrefixRange(base="17.0.0.0", plen=32, count=2500)
    pm.process_event(
        PrefixEvent(
            type=PrefixEventType.ADD_PREFIXES,
            source=PrefixSource.CONFIG,
            ranges=(rng,),
        )
    )
    assert len(kv.persisted) == 3  # chunked, not per-prefix
    assert (pm.counters.get("prefixmgr.range_prefixes") or 0) == 2500
    # steady-state sync touches nothing
    n0 = len(kv.persisted)
    pm._sync_advertisements()
    assert len(kv.persisted) == n0

    # Decision ingests the chunk values as normal prefix keys
    d = mk_decision("cpu")
    kvs = {
        key: Value(
            version=1, originator_id="node-0", value=val
        ).with_hash()
        for _area, key, val in kv.persisted
    }
    d.process_publication(Publication(area=DEFAULT_AREA, key_vals=kvs))
    ps = d.prefix_states[DEFAULT_AREA]
    assert len(ps.prefixes) == 2500
    assert IpPrefix(prefix="17.0.9.195/32") in ps.prefixes  # member 2499

    # withdrawal: tombstone chunks + unset
    pm.process_event(
        PrefixEvent(
            type=PrefixEventType.WITHDRAW_PREFIXES,
            source=PrefixSource.CONFIG,
            ranges=(rng,),
        )
    )
    assert len(kv.unset) == 3
    tomb = kv.persisted[-1]
    from openr_tpu.types.serde import from_wire as _fw

    db = _fw(tomb[2], PrefixDatabase)
    assert db.delete_prefix and len(db.prefix_entries) > 0
    assert (pm.counters.get("prefixmgr.range_prefixes") or 0) == 0


def test_ramp_prefix_state_shapes():
    """The bench's ramp builder: exact counts, anycast fraction in the
    multi table, zero per-prefix ipaddress parses (arithmetic strings
    only — proven by canonical-form equality)."""
    names = [f"node-{i}" for i in range(8)]
    ps = topogen.ramp_prefix_state(names, 1000, anycast_every=100)
    assert len(ps.prefixes) == 1000
    multi = sum(1 for per in ps.prefixes.values() if len(per) == 2)
    assert 0 < multi <= 10
    for p in list(ps.prefixes)[:5]:
        assert IpPrefix.make(p.prefix) == p
