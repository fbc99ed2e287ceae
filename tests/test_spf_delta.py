"""Topology-delta warm-start tests (docs/Decision.md).

The contract under test: a bounded metric-only topology delta (link
flap / metric change) takes the REBUILD_TOPO_DELTA warm-start path —
`decision.rebuild.topo_delta` increments, `decision.rebuild.full` and
the per-area full-solve counter stay flat — and every warm round stays
BYTE-EQUAL with a from-scratch `compute_rib`, proven by seeded
randomized flap sequences (metric increase + decrease, flap-then-
revert, node down, cross-area) on both engines, plus a direct
`warm_spf` vs `run_spf` fuzz.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

from openr_tpu.common.constants import DEFAULT_AREA, adj_key, prefix_key
from openr_tpu.config import Config, NodeConfig
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.oracle import run_spf, warm_spf
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters
from openr_tpu.types.kvstore import Publication, Value
from openr_tpu.types.network import IpPrefix
from openr_tpu.types.serde import to_wire
from openr_tpu.types.topology import PrefixDatabase, PrefixEntry
from openr_tpu.utils import topogen


def run(coro):
    # asyncio.run: closes the loop, cancels leftovers, shuts down
    # async generators — the teardown hygiene the sanitizer checks
    return asyncio.run(coro)


def mk_decision(backend="cpu", name="node-0"):
    cfg = Config(NodeConfig(node_name=name))
    # the native single-root engine has no warm path (its artifact
    # carries no neighbor distance columns): pin the batched kernel so
    # the tpu parametrization exercises the warm kernel deterministically
    cfg.node.decision.native_rib = "off"
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


def prefix_pub(prefix_dbs, area=DEFAULT_AREA, version=1):
    kv = {}
    for db in prefix_dbs:
        for e in db.prefix_entries:
            key = prefix_key(db.this_node_name, area, str(e.prefix.prefix))
            kv[key] = Value(
                version=version,
                originator_id=db.this_node_name,
                value=to_wire(
                    PrefixDatabase(
                        this_node_name=db.this_node_name,
                        prefix_entries=(e,),
                        area=area,
                    )
                ),
            ).with_hash()
    return Publication(area=area, key_vals=kv)


def one_prefix_pub(node, pstr, area=DEFAULT_AREA, version=1):
    return prefix_pub(
        [
            PrefixDatabase(
                this_node_name=node,
                prefix_entries=(PrefixEntry(prefix=IpPrefix(prefix=pstr)),),
                area=area,
            )
        ],
        area=area,
        version=version,
    )


def assert_parity(d, step=None):
    """The warm-start pipeline's published RIB must be byte-equal to a
    from-scratch compute over the same LSDB."""
    ref = d.compute_rib()
    assert d.rib.unicast_routes == ref.unicast_routes, step
    assert d.rib.mpls_routes == ref.mpls_routes, step


def flap_pub(adj_cur, node, k, metric, version, area=DEFAULT_AREA):
    """Re-advertise `node`'s adjacency db with adjacency k's metric set
    to `metric` (one directed link's weight — a metric-only delta)."""
    db = adj_cur[node]
    adjs = list(db.adjacencies)
    adjs[k] = dataclasses.replace(adjs[k], metric=metric)
    db = dataclasses.replace(db, adjacencies=tuple(adjs))
    adj_cur[node] = db
    return adj_pub([db], version=version, area=area)


# ---------------------------------------------------------------- warm_spf


def _random_graph(rng, n):
    adj = {f"n{i}": {} for i in range(n)}
    for i in range(n):
        for _ in range(int(rng.integers(1, 5))):
            j = int(rng.integers(0, n))
            if j != i:
                adj[f"n{i}"][f"n{j}"] = int(rng.integers(1, 12))
    radj = {}
    for u, vs in adj.items():
        for v, w in vs.items():
            radj.setdefault(v, {})[u] = w
    return adj, radj


class _LsStub:
    def __init__(self, overloaded):
        self._over = overloaded

    def is_node_overloaded(self, x):
        return x in self._over


def test_warm_spf_fuzz_vs_run_spf():
    """Direct fuzz: warm_spf after random batched metric changes equals
    run_spf from scratch — dist, preds AND first-hop sets — across
    random graphs, with and without overloaded (no-transit) nodes."""
    rng = np.random.default_rng(7)
    for _trial in range(120):
        n = int(rng.integers(5, 28))
        adj, radj = _random_graph(rng, n)
        overloaded = (
            {f"n{int(rng.integers(1, n))}"} if rng.integers(0, 3) == 0 else set()
        )
        root = "n0"
        old = run_spf(_LsStub(overloaded), root, adj)
        edges = [(u, v) for u, vs in adj.items() for v in vs]
        adj2 = {u: dict(vs) for u, vs in adj.items()}
        radj2 = {u: dict(vs) for u, vs in radj.items()}
        changes, seen = [], set()
        for _ in range(int(rng.integers(1, 4))):
            u, v = edges[int(rng.integers(0, len(edges)))]
            if (u, v) in seen or u == root:
                continue
            seen.add((u, v))
            wo, wn = adj[u][v], int(rng.integers(1, 12))
            if wn == wo:
                continue
            changes.append((u, v, wo, wn))
            adj2[u][v] = wn
            radj2[v][u] = wn
        res = warm_spf(adj2, radj2, old, overloaded, root, changes, n + 1)
        assert res is not None
        spf2, changed, _region = res
        ref = run_spf(_LsStub(overloaded), root, adj2)
        assert spf2.dist == ref.dist
        assert spf2.first_hops == ref.first_hops
        assert spf2.preds == ref.preds
        # the changed-node report covers every route-visible difference
        for x in set(old.dist) | set(ref.dist):
            if old.dist.get(x) != ref.dist.get(x):
                assert x in changed
            if old.first_hops.get(x) != ref.first_hops.get(x):
                assert x in changed


# ------------------------------------------------------------ decision path


def test_metric_change_zero_full_solves_320_grid():
    """Acceptance gate: a single-link metric change on a >=320-node grid
    triggers ZERO full per-area solves — `decision.rebuild.topo_delta`
    increments, `decision.rebuild.full` does not — and the warm RIB is
    byte-equal to from-scratch."""

    async def body():
        d = mk_decision("cpu")
        adj_dbs, prefix_dbs = topogen.grid(18, 18)  # 324 nodes
        assert len(adj_dbs) >= 320
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.full") == 1

        adj_cur = {db.this_node_name: db for db in adj_dbs}
        solves0 = d._area_solves
        d.process_publication(flap_pub(adj_cur, "node-200", 0, 9, 2))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 1
        assert d.counters.get("decision.rebuild.full") == 1  # unchanged
        assert d.counters.get("decision.spf.warm_starts") == 1
        assert d._area_solves == solves0  # zero full area solves
        assert_parity(d)

    run(body())


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_increase_decrease_and_revert(backend):
    """Metric increase, decrease, and flap-then-revert all take the
    warm path with byte parity; after the revert the RIB returns to the
    original routes exactly."""

    async def body():
        d = mk_decision(backend)
        adj_dbs, prefix_dbs = topogen.grid(5, 5, metric=10)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        base_unicast = dict(d.rib.unicast_routes)
        base_mpls = dict(d.rib.mpls_routes)
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        engine0 = d._tpu.warm_solves if d._tpu is not None else None

        # increase
        d.process_publication(flap_pub(adj_cur, "node-7", 1, 30, 2))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 1
        assert_parity(d, "increase")
        # decrease on another link
        d.process_publication(flap_pub(adj_cur, "node-12", 0, 2, 3))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 2
        assert_parity(d, "decrease")
        # revert both (flap-then-revert)
        d.process_publication(flap_pub(adj_cur, "node-7", 1, 10, 4))
        await d._rebuild_routes()
        d.process_publication(flap_pub(adj_cur, "node-12", 0, 10, 5))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") >= 3
        assert d.counters.get("decision.rebuild.full") == 1
        assert_parity(d, "revert")
        assert d.rib.unicast_routes == base_unicast
        assert d.rib.mpls_routes == base_mpls
        if engine0 is not None:
            assert d._tpu.warm_solves > engine0  # the kernel warm path ran

    run(body())


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_randomized_flap_sequence_parity(backend):
    """Parity contract: after EVERY rebuild of a seeded randomized
    flap sequence — metric churn mixed with prefix churn, node-down
    (adj expiry) and node re-advertisement — the incremental RIB equals
    a from-scratch compute_rib, on both engines, and the warm path was
    actually exercised."""

    async def body():
        d = mk_decision(backend)
        adj_dbs, prefix_dbs = topogen.fat_tree(4)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        assert_parity(d, "initial")

        rng = np.random.default_rng(1234)
        names = [db.this_node_name for db in adj_dbs]
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        expired: set[str] = set()
        for step in range(20):
            op = int(rng.integers(0, 10))
            name = names[int(rng.integers(1, len(names)))]  # never self
            if op < 6 and name not in expired:
                # metric flap — the warm-start path
                db = adj_cur[name]
                k = int(rng.integers(0, len(db.adjacencies)))
                pub = flap_pub(
                    adj_cur, name, k, int(rng.integers(1, 32)), step + 2
                )
            elif op < 8:
                # prefix advertise/withdraw riding the same windows
                i = int(rng.integers(0, len(names)))
                pstr = f"10.45.{i}.0/24"
                if rng.integers(0, 2):
                    pub = one_prefix_pub(names[i], pstr, version=step + 2)
                else:
                    pub = Publication(
                        expired_keys=[
                            prefix_key(names[i], DEFAULT_AREA, pstr)
                        ]
                    )
            elif op < 9 and name not in expired:
                # node down via adj-key expiry (structural -> full)
                expired.add(name)
                pub = Publication(expired_keys=[adj_key(name)])
            else:
                # (re-)advertise the node's adjacency db
                expired.discard(name)
                pub = adj_pub([adj_cur[name]], version=step + 2)
            d.process_publication(pub)
            await d._rebuild_routes()
            assert_parity(d, f"step {step}")
        assert d.counters.get("decision.rebuild.topo_delta") > 0

    run(body())


def test_node_down_falls_back_to_full():
    """An adj-key expiry (node down) is structural: the rebuild takes
    the full path, never a stale warm start — and parity holds."""

    async def body():
        d = mk_decision("cpu")
        adj_dbs, prefix_dbs = topogen.ring(5)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        d.process_publication(Publication(expired_keys=[adj_key("node-2")]))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.full") == 2
        assert d.counters.get("decision.rebuild.topo_delta") == 0
        assert_parity(d)

    run(body())


def test_root_incident_flap_falls_back_to_full():
    """A metric change on MY OWN adjacency moves my nexthop interface
    selection: the warm attempt must refuse (decision.spf.warm_fallbacks)
    and the round goes full — with parity."""

    async def body():
        d = mk_decision("cpu")
        adj_dbs, prefix_dbs = topogen.grid(4, 4)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        d.process_publication(flap_pub(adj_cur, "node-0", 0, 21, 2))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.full") == 2
        assert d.counters.get("decision.rebuild.topo_delta") == 0
        assert d.counters.get("decision.spf.warm_fallbacks") == 1
        assert_parity(d)

    run(body())


def test_cross_area_delta_keeps_clean_area_cached():
    """Metric dirt in one area must not touch the other: the clean
    area's RIB is reused (decision.rebuild.cached_areas) while the
    dirty area warm-starts, and the scoped cross-area merge (unicast +
    MPLS labels) stays byte-equal."""

    async def body():
        d = mk_decision("cpu")
        ring_a, pfx_a = topogen.ring(4)
        ring_b, pfx_b = topogen.ring(5, metric=7)
        d.process_publication(adj_pub(ring_a, area="a"))
        d.process_publication(prefix_pub(pfx_a, area="a"))
        d.process_publication(adj_pub(ring_b, area="b"))
        d.process_publication(prefix_pub(pfx_b, area="b"))
        await d._rebuild_routes()
        assert_parity(d, "initial")

        solves0 = d._area_solves
        adj_cur = {db.this_node_name: db for db in ring_b}
        d.process_publication(
            flap_pub(adj_cur, "node-2", 0, 19, 2, area="b")
        )
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 1
        # area "a" AND the (empty) configured default area both reused
        assert d.counters.get("decision.rebuild.cached_areas") == 2
        assert d._area_solves == solves0
        assert_parity(d, "after warm")

    run(body())


def test_topo_delta_disabled_takes_full_path():
    """enable_topo_delta=False forces every topology change down the
    full path (the pre-PR behavior)."""

    async def body():
        d = mk_decision("cpu")
        d.config.node.decision.enable_topo_delta = False
        adj_dbs, prefix_dbs = topogen.grid(4, 4)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        d.process_publication(flap_pub(adj_cur, "node-5", 0, 13, 2))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.full") == 2
        assert d.counters.get("decision.rebuild.topo_delta") == 0
        assert_parity(d)

    run(body())


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_mixed_topo_and_prefix_dirt_one_window(backend):
    """A metric flap and a prefix advertisement coalesced into ONE
    debounce window take a single topo_delta round that lands BOTH
    changes, byte-equal to from-scratch."""

    async def body():
        d = mk_decision(backend)
        adj_dbs, prefix_dbs = topogen.grid(4, 4)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        new = IpPrefix(prefix="10.99.0.0/24")
        d.process_publication(flap_pub(adj_cur, "node-9", 1, 27, 2))
        d.process_publication(one_prefix_pub("node-3", "10.99.0.0/24"))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 1
        assert new in d.rib.unicast_routes
        assert_parity(d)

    run(body())


def test_warm_trim_frees_state_and_rearms():
    """trim_warm_state() reclaims the warm-only artifact memory
    (warm_cache_bytes drops to zero); the next topology delta pays ONE
    re-arming full solve, after which the warm path resumes."""

    async def body():
        d = mk_decision("cpu")
        adj_dbs, prefix_dbs = topogen.grid(5, 5)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        d.process_publication(flap_pub(adj_cur, "node-7", 0, 17, 2))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 1
        grown = d.warm_cache_bytes()
        assert grown > 0  # radj + preds retained
        d.trim_warm_state()
        assert d.warm_cache_bytes() == 0
        # next delta: preds gone -> one full re-arming solve, counted
        # as a warm fallback, with parity intact
        d.process_publication(flap_pub(adj_cur, "node-7", 0, 3, 3))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.full") == 2
        assert d.counters.get("decision.spf.warm_fallbacks") == 1
        assert_parity(d)
        # ...and the path resumes on the flap after that
        d.process_publication(flap_pub(adj_cur, "node-7", 0, 9, 4))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 2
        assert_parity(d)

    run(body())


# ------------------------------------------------ the rebuild's span record


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_last_breakdown_ms_is_a_view_of_the_rebuilds_span_record(backend):
    """`Decision.last_breakdown_ms` comes from the rebuild's span record
    (docs/Monitor.md "Spans"): the five older keys keep their names and
    still nest, every span of the rebuild path is there in every rebuild
    (0.0 where the branch did not run), and on the device engine a
    metric flap fills the warm path's spans and counters."""
    from openr_tpu.monitor import names

    async def body():
        d = mk_decision(backend)
        adj_dbs, prefix_dbs = topogen.grid(5, 5, metric=10)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await asyncio.sleep(0.002)  # the debounce span is open meanwhile
        await d._rebuild_routes()
        full = dict(d.last_breakdown_ms)
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        # raise node-1 -> node-2, a tree edge of root node-0: node-2's
        # distance rises, so the increase cone is not empty
        k = [a.other_node_name for a in adj_cur["node-1"].adjacencies].index(
            "node-2"
        )
        d.process_publication(flap_pub(adj_cur, "node-1", k, 30, 2))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 1
        warm = dict(d.last_breakdown_ms)
        await d._rebuild_routes()  # nothing pending: an empty rebuild
        return d, full, warm, dict(d.last_breakdown_ms)

    d, full, warm, empty = run(body())
    old = {"decode", "apply_snapshot", "compute_diff", "compute_rib", "diff"}
    renamed = {f"decision:{k}" for k in old}
    for bd in (full, warm, empty):
        assert set(bd) == old | (set(names.REBUILD_SPANS) - renamed)
        assert all(v >= 0.0 for v in bd.values())
        assert bd["compute_rib"] + bd["diff"] <= bd["compute_diff"]
        assert (
            bd["decode"] + bd["apply_snapshot"] + bd["compute_diff"]
            + bd["decision:export_counters"] + bd["decision:publish"]
            <= bd["decision:rebuild"]
        )
    assert full["decode"] > 0.0 and full["decision:debounce_wait"] >= 2.0
    assert warm["decision:debounce_wait"] > 0.0
    assert empty["decode"] == 0.0 and empty["decision:debounce_wait"] == 0.0
    warm_spans = (
        "spf:dist_mirror", "spf:warm_cone", "spf:dispatch",
        "spf:warm_scatter", "spf:warm_solve", "spf:warm_unpack",
        "spf:warm_reassemble",
    )
    if backend == "tpu":
        # cold call first: its six phases, under compute_rib
        cold = ("spf:prepare", "spf:batched_solve", "spf:unpack",
                "spf:rib_assembly")
        assert all(full[n] > 0.0 for n in cold)
        assert sum(full[n] for n in cold) <= full["compute_rib"]
        assert all(full[n] == 0.0 for n in warm_spans if n != "spf:dispatch")
        # then the warm start: every host phase of it under a name
        assert all(warm[n] > 0.0 for n in warm_spans)
        assert warm["spf:batched_solve"] == 0.0
        assert (
            warm["spf:to_csr"] + sum(warm[n] for n in warm_spans)
            <= warm["compute_rib"]
        )
        st = d._tpu.spf_kernel_stats
        assert st["warm_tail_rounds"] >= 1 and st["dense_sweeps"] >= 1
        assert st["warm_cone_cells"] >= 1
        assert d._tpu.dev_cache_stats["scatter_calls"] >= 2
        assert d.counters.get("decision.spf.warm_tail_rounds") >= 1
        # a frontier this small never leaves the small capacity; the
        # counters reach decision.spf.* by the loop over every key
        assert st["warm_tail_small_rounds"] == st["warm_tail_rounds"]
        assert 0 <= st["tail_small_rounds"] <= st["tail_rounds"]
        for k in ("warm_tail_small_rounds", "tail_small_rounds"):
            assert d.counters.get(f"decision.spf.{k}") == st[k]
        assert d.counters.get("decision.dev_cache.scatter_calls") >= 2
        # the cold call's six phases; the three of the election feed
        # the stats they always fed, the others make no stat of theirs
        assert set(d._tpu.last_phase_ms) == {
            "prepare", "solve", "unpack", "election", "assembly", "mpls",
        }
        elect = {k for k in d.counters.stats if k.startswith("decision.elect.")}
        assert elect == {
            f"decision.elect.{k}_ms" for k in ("election", "assembly", "mpls")
        }
        # one cold compute_routes call, one reading of its assembly
        assert d.counters.stats["profile.spf:rib_assembly_ms"].count == 1
    else:
        assert all(warm[n] == 0.0 for n in warm_spans)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_rebuild_names_fold_reassembly_hand_off_and_collector(backend):
    """PR 36: `decision:merge_full` / `decision:merge_scope`, the
    children of `spf:warm_reassemble`, the two hand-offs to and from the
    solver thread (record only) and the collector's `spf:gc` /
    `decision:gc` are keys of every rebuild's breakdown; the
    `runtime.gc.*` gauges are exported at the rebuild's edge and only
    grow."""
    import gc

    from openr_tpu.monitor import names, profiling

    new = {
        "decision:merge_full", "decision:merge_scope",
        "decision:thread_start", "decision:thread_return",
        "spf:warm_scope", "spf:general_items", "spf:warm_table_copy",
        "spf:warm_labels", "spf:gc", "decision:gc",
    }
    gauges = (
        "runtime.gc.collections", "runtime.gc.pause_ms",
        "runtime.gc.full_collections", "runtime.gc.full_pause_ms",
    )

    async def body():
        d = mk_decision(backend)
        adj_dbs, prefix_dbs = topogen.grid(5, 5, metric=10)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        with profiling.collect() as rec_full:
            await d._rebuild_routes()
        full = dict(d.last_breakdown_ms)
        seen = [{k: d.counters.snapshot()[k] for k in gauges}]
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        k = [a.other_node_name for a in adj_cur["node-1"].adjacencies].index(
            "node-2"
        )
        d.process_publication(flap_pub(adj_cur, "node-1", k, 30, 2))
        gc.collect()  # under no span: the totals alone
        with profiling.collect() as rec_warm:
            await d._rebuild_routes()
        warm = dict(d.last_breakdown_ms)
        seen.append({k: d.counters.snapshot()[k] for k in gauges})
        return d, full, warm, rec_full, rec_warm, seen

    d, full, warm, rec_full, rec_warm, seen = run(body())
    assert new <= set(names.REBUILD_SPANS)
    for bd in (full, warm):
        assert new <= set(bd) and all(bd[k] >= 0.0 for k in new)
    # the first RIB folds in full; the flap, a scoped round, by the book
    assert full["decision:merge_full"] > 0.0 == full["decision:merge_scope"]
    assert warm["decision:merge_scope"] > 0.0 == warm["decision:merge_full"]
    assert d.counters.get("decision.merge.scoped") == 1
    for bd in (full, warm):
        assert (
            bd["decision:merge_full"] + bd["decision:merge_scope"]
            <= bd["compute_rib"]
        )
        # the hand-offs and the thread's work lie inside the loop's wait
        assert bd["decision:thread_start"] > 0.0
        assert bd["decision:thread_return"] > 0.0
        assert (
            bd["decision:thread_start"] + bd["compute_rib"] + bd["diff"]
            + bd["decision:thread_return"]
            <= bd["compute_diff"] + 1e-6
        )
    for rec in (rec_full, rec_warm):
        at = {n: (p, s, e) for n, p, s, e in rec.spans}
        _p, lo, hi = at["decision:compute_diff"]
        _p, rib_s, _e = at["decision:compute_rib"]
        _p, _s, diff_e = at["decision:diff"]
        for name in ("decision:thread_start", "decision:thread_return"):
            parent, s, e = at[name]
            assert parent == "decision:compute_diff"
            assert lo <= s <= e <= hi
        assert at["decision:thread_start"][2] <= rib_s
        assert diff_e <= at["decision:thread_return"][1]
    parents = {n: p for n, p, _s, _e in rec_warm.spans}
    assert parents["decision:merge_scope"] == "decision:compute_rib"
    assert (
        {n: p for n, p, _s, _e in rec_full.spans}["decision:merge_full"]
        == "decision:compute_rib"
    )
    if backend == "tpu":
        parts = ("spf:warm_scope", "spf:general_items", "spf:warm_table_copy",
                 "spf:warm_labels")
        assert all(parents[n] == "spf:warm_reassemble" for n in parts)
        assert parents["spf:unicast_general"] == "spf:warm_reassemble"
        assert all(warm[n] > 0.0 for n in parts)
        assert all(full[n] == 0.0 for n in parts)
        assert (
            sum(warm[n] for n in parts) + warm["spf:unicast_general"]
            + warm["spf:ksp"] <= warm["spf:warm_reassemble"]
        )
        # the solver's copy of the totals, exported by the loop over
        # every key of spf_kernel_stats
        st = d._tpu.spf_kernel_stats
        assert st["gc_full_collections"] >= 1 and st["gc_pause_ms"] > 0.0
        assert d.counters.get("decision.spf.gc_full_collections") >= 1
    # cumulative and process-wide: both readings there, the later no
    # smaller, and the forced collection between them counted
    first, second = seen
    assert all(second[k] >= first[k] >= 0 for k in gauges)
    for k in ("runtime.gc.full_collections", "runtime.gc.full_pause_ms"):
        assert second[k] > first[k]
    assert second["runtime.gc.pause_ms"] >= second["runtime.gc.full_pause_ms"]


# ------------------------------------------------- the compiled patch scatter


def _split_tables_of(csr):
    """The split tables built from scratch out of a (patched) CSR."""
    from openr_tpu.ops.spf_split import build_split_tables

    return build_split_tables(
        csr.edge_src, csr.edge_dst, csr.edge_metric, csr.num_nodes
    )


def _adj_index(adj_cur, node, other):
    """Position of `node`'s adjacency to `other`, as flap_pub takes it."""
    return [a.other_node_name for a in adj_cur[node].adjacencies].index(other)


def _star_ls():
    """Hub n00 with 20 leaves: the hub's 20 in-edges overflow the split
    tables' base width (8), so its patches split between base_wgt and
    ov_wgt; every leaf's one in-edge sits in the base table."""
    from openr_tpu.decision.linkstate import LinkState
    from openr_tpu.types.topology import Adjacency, AdjacencyDatabase

    def adj(me, other, metric):
        return Adjacency(
            other_node_name=other, if_name=f"{me}-{other}",
            other_if_name=f"{other}-{me}", metric=metric,
        )

    leaves = [f"n{i:02d}" for i in range(1, 21)]
    dbs = {
        "n00": AdjacencyDatabase(
            this_node_name="n00",
            adjacencies=tuple(adj("n00", x, 10) for x in leaves),
        )
    }
    for x in leaves:
        dbs[x] = AdjacencyDatabase(
            this_node_name=x, adjacencies=(adj(x, "n00", 10),)
        )
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_db(db)

    def set_metric(me, other, metric):
        db = dbs[me]
        dbs[me] = dataclasses.replace(db, adjacencies=tuple(
            dataclasses.replace(a, metric=metric)
            if a.other_node_name == other else a
            for a in db.adjacencies
        ))
        assert ls.update_adjacency_db(dbs[me])

    return ls, set_metric


@pytest.fixture(scope="module")
def patched_star():
    """Both table sets resident (the split tables and KSP's), then ONE
    journal suffix of four patches scattered into both: three distinct
    cells (padded to a batch of 8), one of them patched twice with
    different values, two in the hub's row on either side of the base
    width. Returns {site: (what the device holds, the numpy reference)}:
    the reference applies the suffix in journal order to the unpatched
    host tables."""
    import jax.numpy as jnp

    from openr_tpu.decision.spf_backend import (
        TpuSpfSolver,
        _warm_scatter_pad,
    )
    from openr_tpu.ops.spf import INF_DIST

    ls, set_metric = _star_ls()
    solver = TpuSpfSolver(native_rib="off")
    csr0 = ls.to_csr()
    for want in ("dense", "split"):
        solver._device_arrays(csr0, want)
    t0 = _split_tables_of(csr0)
    w, ov_pos = t0["base_nbr"].shape[1], t0["ov_pos"]
    assert w == 8 and ov_pos[csr0.name_to_id["n00"]] >= 0
    ref = {
        "dense_wgt": csr0.dense_tables()[1].copy(),
        "split_base_wgt": t0["base_wgt"].copy(),
        "split_ov_wgt": t0["ov_wgt"].copy(),
    }
    set_metric("n03", "n00", 7)   # hub row, a base column
    set_metric("n15", "n00", 9)   # hub row, an overflow column
    ls.to_csr()                   # journalled, no solve: not on the device
    set_metric("n03", "n00", 4)   # the same cell again, another value
    set_metric("n00", "n05", 6)   # a leaf's row
    csr = ls.to_csr()
    suffix = csr.patches[len(csr0.patches):]
    assert [p.metric for p in suffix] == [7, 9, 4, 6]
    assert len({p.edge_idx for p in suffix}) == 3
    assert {p.dense_col < w for p in suffix} == {True, False}
    calls0 = solver.dev_cache_stats["scatter_calls"]
    solver._device_arrays(csr, "split")
    # one program a patched array: dense, split base, split overflow
    assert solver.dev_cache_stats["scatter_calls"] - calls0 == 3
    for p in suffix:
        ref["dense_wgt"][p.dense_row, p.dense_col] = p.metric
        if p.dense_col < w:
            ref["split_base_wgt"][p.dense_row, p.dense_col] = p.metric
        else:
            ref["split_ov_wgt"][ov_pos[p.dense_row], p.dense_col - w] = p.metric
    # the reference is the patched CSR's own tables, built from scratch
    t1 = _split_tables_of(csr)
    np.testing.assert_array_equal(ref["dense_wgt"], csr.dense_tables()[1])
    np.testing.assert_array_equal(ref["split_base_wgt"], t1["base_wgt"])
    np.testing.assert_array_equal(ref["split_ov_wgt"], t1["ov_wgt"])
    sets = solver._dev[csr.base_version]["sets"]
    got = {
        "dense_wgt": sets["dense"]["wgt"],
        "split_base_wgt": sets["split"]["base_wgt"],
        "split_ov_wgt": sets["split"]["ov_wgt"],
    }
    # the warm start's site: a cone's cells to INF_DIST, padded to the
    # first tier by repeating the last cell; the matrix handed in is the
    # previous artifact's and keeps its values (no donation)
    rng = np.random.default_rng(5)
    host = rng.integers(0, 1000, (t0["vp"], 8)).astype(np.int32)
    dist_dev = jnp.asarray(host)
    cells = [(3, 0), (7, 0), (7, 5), (t0["vp"] - 2, 7)]
    nb = _warm_scatter_pad(len(cells))
    rows = np.full(nb, cells[-1][0], np.int32)
    cols = np.full(nb, cells[-1][1], np.int32)
    rows[: len(cells)], cols[: len(cells)] = zip(*cells)
    got["dist_matrix"] = solver._set(dist_dev, (rows, cols), INF_DIST)
    ref["dist_matrix"] = host.copy()
    ref["dist_matrix"][rows, cols] = INF_DIST
    np.testing.assert_array_equal(np.asarray(dist_dev), host)
    return {k: (np.asarray(got[k]), ref[k]) for k in ref}


@pytest.mark.parametrize(
    "site",
    ["dense_wgt", "split_base_wgt", "split_ov_wgt", "dist_matrix"],
)
def test_compiled_scatter_equals_numpy_reference(patched_star, site):
    """Each of the four sites that patch a device array through
    `_scatter_set` holds what numpy's sequential assignment gives: a
    padded batch, one cell twice in a suffix (the last value wins), a
    suffix split between the base and the overflow table."""
    got, ref = patched_star[site]
    assert got.dtype == ref.dtype and got.shape == ref.shape
    np.testing.assert_array_equal(got, ref)


def _split_tables_equal_patched_csr(d):
    """The solver's resident split tables against tables built from
    scratch out of the LSDB's patched CSR."""
    (ls, _ps), = d._snapshot_states().values()
    csr = ls.to_csr()
    want = _split_tables_of(csr)
    dset = d._tpu._dev[csr.base_version]["sets"]["split"]
    for k in ("base_wgt", "ov_wgt"):
        np.testing.assert_array_equal(np.asarray(dset[k]), want[k], k)


def test_flap_reverted_in_one_window_then_reflapped():
    """A flap fully reverted inside one debounce window changes nothing:
    the rebuild solves nothing and its patch stays in the journal. (The
    LSDB has to see both values for that: Decision keeps one value a key
    until something drains the buffer — an LSDB reader, or the rebuild's
    own decode await with the revert arriving during it.) The next flap
    of the same link then scatters a suffix that names the cell twice,
    [(e, 10), (e, 30)] — the device tables must hold the last value, and
    the RIB must equal the oracle's after each rebuild."""
    from openr_tpu.decision.oracle import compute_routes as oracle_routes

    def assert_oracle(d, step):
        (ls, ps), = d._snapshot_states().values()
        ref = oracle_routes(ls, ps, d.node_name)
        assert d.rib.unicast_routes == ref.unicast_routes, step
        assert d.rib.mpls_routes == ref.mpls_routes, step

    async def body():
        d = mk_decision("tpu")
        adj_dbs, prefix_dbs = topogen.grid(5, 5, metric=10)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        assert_oracle(d, "initial")
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        k = _adj_index(adj_cur, "node-1", "node-2")
        st = d._tpu.dev_cache_stats
        warm0, patches0 = d._tpu.warm_solves, st["patches"]
        # up and back down inside one window: one rebuild, no solve
        d.process_publication(flap_pub(adj_cur, "node-1", k, 30, 2))
        d._drain_pending()  # as every LSDB reader does
        d.process_publication(flap_pub(adj_cur, "node-1", k, 10, 3))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.topo_delta") == 1
        assert d.counters.get("decision.rebuild.full") == 1
        assert d._tpu.warm_solves == warm0 and st["patches"] == patches0
        assert_oracle(d, "reverted in one window")
        # the same link again: both patches of the cell in one suffix
        d.process_publication(flap_pub(adj_cur, "node-1", k, 30, 4))
        await d._rebuild_routes()
        assert d.counters.get("decision.rebuild.full") == 1
        assert d._tpu.warm_solves == warm0 + 1
        assert st["patches"] == patches0 + 1
        (ls, _ps), = d._snapshot_states().values()
        assert [p.metric for p in ls.to_csr().patches[-2:]] == [10, 30]
        assert_oracle(d, "flapped again")
        _split_tables_equal_patched_csr(d)
        # and back: a lowered edge, the tables follow
        d.process_publication(flap_pub(adj_cur, "node-1", k, 10, 5))
        await d._rebuild_routes()
        assert d._tpu.warm_solves == warm0 + 2
        assert_oracle(d, "restored")
        _split_tables_equal_patched_csr(d)

    run(body())


def test_a_warm_flap_patches_with_one_program_a_scatter(monkeypatch):
    """After warm-up a warm flap compiles nothing, and what it dispatches
    inside `spf:patch_scatter` and `spf:warm_scatter` is `_scatter_set`
    alone, at most two of them. The programs are counted through the
    compile ledger: with jax's caches dropped, every program a span
    dispatches compiles again and so shows there by name (an eager
    `.at[].set` shows as less, add, select_n, ..., scatter)."""
    import contextlib

    import jax

    from openr_tpu.monitor import compile_ledger, profiling

    led = compile_ledger.ledger()
    assert led.installed
    scatter_spans = ("spf:patch_scatter", "spf:warm_scatter")
    seen: dict[str, dict[str, int]] = {n: {} for n in scatter_spans}
    real_annotate = profiling.annotate

    @contextlib.contextmanager
    def spy(name, counters=None):
        before = led.snapshot()
        with real_annotate(name, counters) as span:
            yield span
        if name in seen:
            for fn, n in before.delta(led.snapshot()).items():
                seen[name][fn] = seen[name].get(fn, 0) + n

    async def body():
        d = mk_decision("tpu")
        adj_dbs, prefix_dbs = topogen.grid(5, 5, metric=10)
        d.process_publication(adj_pub(adj_dbs))
        d.process_publication(prefix_pub(prefix_dbs))
        await d._rebuild_routes()
        adj_cur = {db.this_node_name: db for db in adj_dbs}
        k = _adj_index(adj_cur, "node-1", "node-2")
        version = 1

        async def flap(metric):
            nonlocal version
            version += 1
            d.process_publication(
                flap_pub(adj_cur, "node-1", k, metric, version)
            )
            await d._rebuild_routes()

        for metric in (30, 10, 30, 10):  # warm-up: raise and restore
            await flap(metric)
        st = d._tpu.dev_cache_stats
        before, calls0 = led.snapshot(), st["scatter_calls"]
        await flap(30)
        assert before.delta(led.snapshot()) == {}  # compiled nothing
        assert 1 <= st["scatter_calls"] - calls0 <= 2
        await flap(10)
        # the same two flaps again, every program compiling anew
        jax.clear_caches()
        monkeypatch.setattr(profiling, "annotate", spy)
        calls0 = st["scatter_calls"]
        await flap(30)
        await flap(10)
        assert d.counters.get("decision.rebuild.full") == 1
        assert_parity(d)
        return st["scatter_calls"] - calls0

    calls = run(body())
    # a raise: one table patch, one cone scatter; a restore: its table
    # patch alone (nothing rises, the cone is empty)
    assert calls == 3
    assert set(seen["spf:patch_scatter"]) == {"_scatter_set"}
    assert set(seen["spf:warm_scatter"]) == {"_scatter_set"}
    # two programs in all (the table's shape and the matrix's), compiled
    # once each however many flaps dispatch them
    assert seen["spf:patch_scatter"]["_scatter_set"] == 1
    assert seen["spf:warm_scatter"]["_scatter_set"] == 1
