"""BASELINE config 5: incremental SPF under sustained link-flap churn.

Measures, on one Decision module fed through its real publication path:
  * steady-state recompute latency p50/p99 (full LSDB → RIB, using the
    incremental CSR patch journal + device-array cache),
  * flap → RouteUpdate end-to-end latency (publication push to route
    delta emitted, including debounce),
  * coalescing: flaps absorbed per recompute (debounce effectiveness).

Run: python benchmarks/bench_churn.py [--nodes 1280] [--flaps-per-sec 1000]
     [--seconds 10]
Prints one JSON line (same contract as bench.py).

reference analogue: openr/decision/tests/DecisionBenchmark.cpp † measures
full rebuilds on synthetic grids; the reference has no incremental path —
this harness exists to show churn does NOT cost a full rebuild here.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from openr_tpu.common.tasks import guard_task, reap  # noqa: E402


def _bench_trace():
    """OPENR_BENCH_TRACE=<dir> xprof trace hook shared by the measured
    stages (same contract as bench.py's headline loop): a no-op when
    unset or the profiler is unavailable (monitor/profiling.py)."""
    from openr_tpu.monitor import profiling

    return profiling.trace(os.environ.get("OPENR_BENCH_TRACE"))


def build_decision(
    adj_dbs, prefix_dbs, debounce_min=None, debounce_max=None,
    solver="tpu", counters=None, areas=("0",),
):
    from openr_tpu.config import Config
    from openr_tpu.decision.decision import Decision
    from openr_tpu.messaging import ReplicateQueue
    from openr_tpu.types.kvstore import Publication, Value
    from openr_tpu.types.serde import to_wire

    cfg = Config.default(adj_dbs[0].this_node_name)
    if debounce_min is not None:
        cfg.node.decision.debounce_min_ms = debounce_min
    if debounce_max is not None:
        cfg.node.decision.debounce_max_ms = debounce_max
    pubs = ReplicateQueue(name="pubs")
    routes = ReplicateQueue(name="routes")
    dec = Decision(
        cfg, pubs.get_reader("d"), routes, solver=solver, counters=counters
    )

    def pub_for(db, version=1, area="0"):
        return Publication(
            area=area,
            key_vals={
                f"adj:{db.this_node_name}": Value(
                    version=version,
                    originator_id=db.this_node_name,
                    value=to_wire(db),
                ).with_hash()
            },
        )

    # the same adjacency plane published under every requested area
    # (multi-area work bench: a dual-plane topology so the cross-area
    # merge book genuinely selects across two full per-area tables)
    for area in areas:
        for db in adj_dbs:
            dec.process_publication(pub_for(db, area=area))
    from openr_tpu.common import constants as C

    for pdb in prefix_dbs:
        for entry in pdb.prefix_entries:
            dec.process_publication(
                Publication(
                    area=areas[0],
                    key_vals={
                        C.prefix_key(
                            pdb.this_node_name, areas[0], str(entry.prefix)
                        ): Value(
                            version=1,
                            originator_id=pdb.this_node_name,
                            value=to_wire(pdb),
                        ).with_hash()
                    },
                )
            )
    return dec, pubs, routes, pub_for


async def churn(
    dec, pubs, routes, pub_for, adj_dbs, flaps_per_sec, seconds, burst=10
):
    """Flap link metrics at the target rate while Decision runs live.

    `burst` flaps are delivered back-to-back per wakeup (aggregate rate
    unchanged); real KvStore floods deliver publication BATCHES. The
    inter-wakeup gap (burst / flaps_per_sec) is the protocol's most
    load-bearing knob: gaps at or below Decision's debounce MIN
    (default 10 ms) re-defer the coalescing window on every poke, so
    each cycle runs to the debounce MAX cap (default 250 ms) — the
    by-design saturating-churn regime (~250-flap batches, flap→RIB
    ≈ max/2 + recompute). Gaps above the min (burst 20 at 1 kHz ⇒
    20 ms) fire the min-debounce after every burst — the low-latency
    regime. See the BASELINE.md config-5 protocol note; traced
    poke-by-poke in round 5."""
    import dataclasses

    from openr_tpu.messaging import QueueClosedError

    await dec.start()
    reader = routes.get_reader("bench")
    # LSDB was loaded synchronously before start: trigger + await the
    # first full RIB (includes the one-time jit compile)
    dec.debounce.poke()
    await asyncio.wait_for(dec.rib_computed.wait(), 600)

    from openr_tpu.monitor import perf

    rng = np.random.default_rng(7)
    flap_t: dict[int, float] = {}  # flap seq -> send time
    got_t: list[float] = []  # flap→update latencies
    trace_ms: list[float] = []  # PerfEvents-derived flap→update totals
    spf_ms: list[float] = []
    breakdown: dict[str, list[float]] = {}
    versions = {db.this_node_name: 1 for db in adj_dbs}
    n_flaps = 0
    stop = time.perf_counter() + seconds
    interval = 1.0 / flaps_per_sec

    async def drain():
        while True:
            try:
                upd = await reader.get()
            except QueueClosedError:
                return
            now = time.perf_counter()
            # only credit flaps published BEFORE the snapshot behind this
            # update — later flaps land in the NEXT rebuild and counting
            # them here would deflate the reported latency
            cutoff = dec._last_emitted_snapshot_t0
            for seq, t0 in list(flap_t.items()):
                if t0 <= cutoff:
                    got_t.append((now - t0) * 1e3)
                    del flap_t[seq]
            # trace-derived latency: the per-stage-stamped PerfEvents the
            # sampled flaps carried through Decision (KVSTORE_FLOODED →
            # ROUTE_UPDATE_SENT), independent of this loop's wall clock
            for pe in upd.perf_events:
                trace_ms.append(pe.total_ms())

    drainer = guard_task(
        asyncio.ensure_future(drain()), owner="bench_churn.drain"
    )
    # Pre-generate the flap publications: in production the serialization
    # happens at each flapping link's OWN router (LinkMonitor persistKey);
    # this node only ever sees the serialized value arrive from KvStore.
    # Building them in the send loop would bill the remote originators'
    # encode cost to the node under test.
    max_flaps = int(flaps_per_sec * seconds * 1.2) + 100
    pregen = []
    for _ in range(max_flaps):
        i = int(rng.integers(0, len(adj_dbs)))
        db = adj_dbs[i]
        k = int(rng.integers(0, len(db.adjacencies)))
        new_adjs = list(db.adjacencies)
        a = new_adjs[k]
        new_adjs[k] = dataclasses.replace(
            a, metric=int(rng.integers(1, 64))
        )
        db = dataclasses.replace(db, adjacencies=tuple(new_adjs))
        adj_dbs[i] = db
        versions[db.this_node_name] += 1
        pregen.append(pub_for(db, version=versions[db.this_node_name]))

    next_send = time.perf_counter()
    base_spf_runs = dec._spf_runs
    last_runs = dec._spf_runs
    no_change_flaps = [0]
    stop = time.perf_counter() + seconds  # exclude pregen time
    while time.perf_counter() < stop and n_flaps < max_flaps:
        for _ in range(burst):
            if n_flaps >= max_flaps:
                break
            flap_t[n_flaps] = time.perf_counter()
            if n_flaps % 50 == 0:
                # sampled tracing (1-in-50): enough samples for a p50
                # without letting trace bookkeeping distort the very
                # hot path this bench measures
                pregen[n_flaps].perf_events = perf.PerfEvents.start(
                    perf.KVSTORE_FLOODED, node="bench"
                )
            dec.process_publication(pregen[n_flaps])
            n_flaps += 1
        dec.debounce.poke()
        # one recompute-latency sample PER RECOMPUTE (flap-weighted
        # sampling would duplicate the pre-churn value hundreds of times)
        if dec._spf_runs != last_runs:
            last_runs = dec._spf_runs
            spf_ms.append(dec._last_spf_ms)
            for k, v in dec.last_breakdown_ms.items():
                breakdown.setdefault(k, []).append(v)
        # flaps proven to have produced no route change (their rebuild
        # completed without emitting) are dropped, not timed forever
        emitted, completed = (
            dec._last_emitted_snapshot_t0, dec._last_completed_snapshot_t0
        )
        if completed > emitted:
            for seq, t in list(flap_t.items()):
                if emitted < t <= completed:
                    del flap_t[seq]
                    no_change_flaps[0] += 1
        next_send += interval * burst
        delay = next_send - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        else:
            await asyncio.sleep(0)  # yield so Decision can run
    # let the tail drain
    await asyncio.sleep(1.0)
    spf_runs = dec._spf_runs - base_spf_runs
    await reap(drainer)
    await dec.stop()
    return (
        n_flaps, spf_runs, spf_ms, got_t, no_change_flaps[0], breakdown,
        trace_ms,
    )


def measure_prefix_churn(
    nodes: int = 80,
    rounds: int = 120,
    burst: int = 8,
    solver: str = "cpu",
    force_full: bool = False,
    seed: int = 3,
    warmup_rounds: int = 4,
    work_accounting: bool = True,
):
    """Prefix-only churn microbench: the dirty-scoped rebuild's headline.

    Fixed fat-tree topology; a rotating pool of extra /24s is
    re-advertised / withdrawn through the REAL publication path, and the
    rebuild coroutine is driven directly (no debounce timing noise) —
    each round is `burst` prefix events then one rebuild, sampling
    `Decision._last_spf_ms`. On the scoped pipeline every round is a
    `decision.rebuild.prefix_only` with ZERO SPF solves; with
    `force_full=True` the SAME workload runs down the from-scratch path
    (`Decision.force_full_rebuild`) for the speedup comparison.

    Returns a dict with `prefix_churn_p50_ms`/p99 plus the pipeline
    counters proving which path ran (`rebuild_prefix_only`,
    `rebuild_full`, `area_solves`, `engine_solves`).
    """
    from openr_tpu.common import constants as C
    from openr_tpu.monitor import Counters, compile_ledger, work_ledger
    from openr_tpu.types.kvstore import Publication, Value
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.serde import to_wire
    from openr_tpu.types.topology import PrefixDatabase, PrefixEntry
    from openr_tpu.utils import topogen

    led = compile_ledger.install()
    work_ledger.reset()
    work_ledger.set_enabled(work_accounting)
    k = max(4, int(round((nodes * 4 / 5) ** 0.5 / 2)) * 2)
    adj_dbs, prefix_dbs = topogen.fat_tree(k, metric=10)
    counters = Counters()
    dec, _pubs, _routes, _pub_for = build_decision(
        adj_dbs, prefix_dbs, solver=solver, counters=counters
    )
    dec.force_full_rebuild = force_full
    rng = np.random.default_rng(seed)
    names = [db.this_node_name for db in adj_dbs]
    pool_n = 200  # rotating advertise/withdraw pool, one /24 each
    advertised = [False] * pool_n
    versions: dict[str, int] = {}

    async def run():
        samples: list[float] = []
        await dec._rebuild_routes()  # initial full build (jit compile)
        solves0 = dec._area_solves
        for r in range(rounds):
            if r == warmup_rounds:
                # post-warmup rounds must be pure jit-cache hits: any
                # later XLA compile is a ledger violation the smoke
                # lane exits 1 on; the work ledger's steady-state
                # window opens at the same boundary
                led.mark_warm()
                work_ledger.mark_warm()
            for _ in range(burst):
                i = int(rng.integers(0, pool_n))
                node = names[i % len(names)]
                pstr = f"10.77.{i}.0/24"
                key = C.prefix_key(node, "0", pstr)
                if advertised[i]:
                    pub = Publication(area="0", expired_keys=[key])
                else:
                    versions[key] = versions.get(key, 0) + 1
                    pub = Publication(
                        area="0",
                        key_vals={
                            key: Value(
                                version=versions[key],
                                originator_id=node,
                                value=to_wire(
                                    PrefixDatabase(
                                        this_node_name=node,
                                        prefix_entries=(
                                            PrefixEntry(
                                                prefix=IpPrefix(prefix=pstr)
                                            ),
                                        ),
                                        area="0",
                                    )
                                ),
                            ).with_hash()
                        },
                    )
                advertised[i] = not advertised[i]
                dec.process_publication(pub)
            await dec._rebuild_routes()
            if r >= warmup_rounds:
                samples.append(dec._last_spf_ms)
        return samples, solves0

    samples, solves0 = asyncio.new_event_loop().run_until_complete(run())
    steady_compiles = led.compiles_since_warm()
    led.reset_warm()
    work = work_ledger.since_warm() if work_accounting else {}
    work_ledger.reset_warm()
    work_ledger.set_enabled(True)
    arr = np.array(samples) if samples else np.array([0.0])
    engine_solves = (
        dec._tpu.solve_count if dec._tpu is not None else dec._area_solves
    )
    return {
        "prefix_churn_p50_ms": round(float(np.percentile(arr, 50)), 3),
        "prefix_churn_p99_ms": round(float(np.percentile(arr, 99)), 3),
        "steady_state_compiles": sum(steady_compiles.values()),
        "steady_state_compile_fns": sorted(steady_compiles),
        # per-stage steady-state work attribution (docs/Monitor.md
        # "Work ledger"): touched/delta/ratio since the warm mark
        "work": work,
        "work_accounting": work_accounting,
        "nodes": len(adj_dbs),
        "rounds": rounds,
        "burst": burst,
        "engine": solver,
        "forced_full": force_full,
        "rebuild_prefix_only": int(
            counters.get("decision.rebuild.prefix_only")
        ),
        "rebuild_full": int(counters.get("decision.rebuild.full")),
        "area_solves": dec._area_solves,
        "churn_area_solves": dec._area_solves - solves0,
        "engine_solves": engine_solves,
    }


def measure_topo_churn(
    nodes: int = 320,
    rounds: int = 60,
    solver: str = "cpu",
    force_full: bool = False,
    seed: int = 5,
    warmup_rounds: int = 2,
    check_parity_every: int = 0,
    revert_every: int = 4,
):
    """Seeded link-flap / metric-change storm microbench: the
    topology-delta warm-start's headline (`--topo-churn`).

    Fixed grid topology; each round flaps ONE random non-root link's
    metric through the REAL publication path and drives the rebuild
    coroutine directly (no debounce timing noise), sampling
    `Decision._last_spf_ms`. Every `revert_every`-th round reverts the
    previous flap (flap-then-revert, the convergence-critical shape).
    On the warm pipeline every round is a `decision.rebuild.topo_delta`
    with zero full area solves; `force_full=True` runs the SAME
    workload down the from-scratch path for the speedup comparison.

    With `check_parity_every=N > 0`, every Nth round's published RIB is
    compared byte-for-byte against a from-scratch `compute_rib` — the
    CI smoke lane's gate.

    Returns `topo_churn_p50_ms`/p99 plus the counters proving which
    path ran (`rebuild_topo_delta`, `rebuild_full`, `warm_starts`,
    `engine_solves`, `churn_area_solves`) and `parity` ("ok" /
    "MISMATCH:<round>" / "unchecked").
    """
    import dataclasses

    from openr_tpu.monitor import Counters, compile_ledger, work_ledger
    from openr_tpu.utils import topogen

    led = compile_ledger.install()
    work_ledger.reset()
    side = max(2, int(round(nodes ** 0.5)))
    adj_dbs, prefix_dbs = topogen.grid(side, side)
    counters = Counters()
    dec, _pubs, _routes, pub_for = build_decision(
        adj_dbs, prefix_dbs, solver=solver, counters=counters
    )
    if solver == "tpu":
        # the native single-root engine has no warm-start path (its
        # artifact carries no neighbor distance columns): measure the
        # batched-kernel pipeline the delta path targets
        if dec._tpu is not None:
            dec._tpu.native_rib = "off"
    dec.force_full_rebuild = force_full
    rng = np.random.default_rng(seed)
    adj_cur = {db.this_node_name: db for db in adj_dbs}
    names = [db.this_node_name for db in adj_dbs]
    versions = {n: 1 for n in names}
    parity = ["unchecked"]

    def flap(node: str, k: int, metric: int):
        db = adj_cur[node]
        adjs = list(db.adjacencies)
        adjs[k] = dataclasses.replace(adjs[k], metric=metric)
        db = dataclasses.replace(db, adjacencies=tuple(adjs))
        adj_cur[node] = db
        versions[node] += 1
        dec.process_publication(pub_for(db, version=versions[node]))

    async def run():
        samples: list[float] = []
        await dec._rebuild_routes()  # initial full build (jit compile)
        solves0 = dec._area_solves
        parity_solves = 0
        last: tuple | None = None
        for r in range(rounds):
            if r == warmup_rounds:
                # zero-steady-state-recompile gate (ci.sh smoke lane):
                # every post-warmup round — warm kernel, cone scatter,
                # patch scatter, parity compute_rib — must hit the jit
                # cache; the ledger counts anything that doesn't
                led.mark_warm()
                work_ledger.mark_warm()
            if last is not None and revert_every and r % revert_every == 0:
                node, k, old_metric = last
                flap(node, k, old_metric)  # flap-then-revert
                last = None
            else:
                # never the RIB root: a root-incident metric change
                # legitimately falls back to full (nexthop slot metrics
                # move) — that case is covered by tests, not the bench
                node = names[int(rng.integers(1, len(names)))]
                db = adj_cur[node]
                k = int(rng.integers(0, len(db.adjacencies)))
                old_metric = int(db.adjacencies[k].metric)
                new_metric = old_metric
                while new_metric == old_metric:
                    # a draw equal to the current metric would be a
                    # no-op round (no rebuild → stale latency sample,
                    # missed counter) — re-roll, still seed-determined
                    new_metric = int(rng.integers(1, 64))
                flap(node, k, new_metric)
                last = (node, k, old_metric)
            await dec._rebuild_routes()
            if r >= warmup_rounds:
                samples.append(dec._last_spf_ms)
            if check_parity_every and r % check_parity_every == 0:
                before = dec._area_solves
                ref = dec.compute_rib()
                parity_solves += dec._area_solves - before
                if (
                    dec.rib.unicast_routes != ref.unicast_routes
                    or dec.rib.mpls_routes != ref.mpls_routes
                ):
                    parity[0] = f"MISMATCH:{r}"
                    break
                if parity[0] == "unchecked":
                    parity[0] = "ok"
        return samples, solves0, parity_solves

    # OPENR_BENCH_TRACE=<dir> captures an xprof trace of the churn rounds
    with _bench_trace():
        samples, solves0, parity_solves = asyncio.run(run())
    steady_compiles = led.compiles_since_warm()
    led.reset_warm()
    # NOTE: with check_parity_every > 0 the from-scratch compute_rib
    # parity calls land inside the steady window, so the spf_full row
    # includes the parity solves' honest full-table work (single-area
    # bench: no merge fold runs, scoped or full)
    work = work_ledger.since_warm()
    work_ledger.reset_warm()
    arr = np.array(samples) if samples else np.array([0.0])
    engine_solves = (
        dec._tpu.solve_count if dec._tpu is not None else dec._area_solves
    )
    warm_engine = dec._tpu.warm_solves if dec._tpu is not None else None
    return {
        "topo_churn_p50_ms": round(float(np.percentile(arr, 50)), 3),
        "topo_churn_p99_ms": round(float(np.percentile(arr, 99)), 3),
        "steady_state_compiles": sum(steady_compiles.values()),
        "steady_state_compile_fns": sorted(steady_compiles),
        "work": work,
        "nodes": len(adj_dbs),
        "rounds": rounds,
        "engine": solver,
        "forced_full": force_full,
        "rebuild_topo_delta": int(
            counters.get("decision.rebuild.topo_delta")
        ),
        "rebuild_full": int(counters.get("decision.rebuild.full")),
        "warm_starts": int(counters.get("decision.spf.warm_starts")),
        "warm_fallbacks": int(
            counters.get("decision.spf.warm_fallbacks")
        ),
        "area_solves": dec._area_solves,
        # full-area solves the CHURN itself cost (parity-check
        # compute_rib calls excluded): zero on the warm pipeline
        "churn_area_solves": dec._area_solves - solves0 - parity_solves,
        "engine_solves": engine_solves,
        "engine_warm_solves": warm_engine,
        "parity": parity[0],
    }


class _NullKv:
    """KvStoreClient stub for the work bench's PrefixManager: the
    redistribution book's walks are the measurement; re-advertisement
    back into KvStore is out of scope (and would need a full cluster)."""

    def persist_key(self, area, key, value, ttl_ms=0):
        pass

    def unset_key(self, area, key):
        pass


def measure_work_churn(
    nodes: int = 320,
    prefixes: int = 100_000,
    rounds: int = 24,
    burst: int = 16,
    mode: str = "prefix",
    solver: str = "tpu",
    seed: int = 9,
    warmup_rounds: int = 4,
):
    """Work-ledger attribution bench (`--work-bench`): the full route
    dataflow — dirt → SPF → election → assembly → cross-area merge →
    diff → FIB programming → PrefixManager redistribution — under
    steady churn, with every stage's touched-entity count accounted
    against its input delta (docs/Monitor.md "Work ledger").

    Unlike the prefix/topo microbenches this one is built so the whole
    delta pipeline — including the two formerly-O(routes) stages — runs
    end to end every round:

      * a dual-plane two-area topology (the same adjacency graph
        published under areas "0" and "1", the static prefix pool split
        between them) makes every scoped rebuild exercise the
        cross-area delta merge book (merge_scope_delta patching the
        live RIB in place);
      * a real PrefixManager in the ABR role (two configured areas,
        stub KvStore client) folds every RouteUpdate through
        `fold_rib_update` + `_sync_advertisements` — delta-native entry
        books since ISSUE 17, touched ≈ the update's own churn;
      * a real Fib (MockFibHandler) programs every RouteUpdate through
        the delta book, pinning `work.fib.ratio` at 1.

    `mode="prefix"` churns a rotating advertise/withdraw pool in area
    "0"; `mode="topo"` flaps one link metric per round in area "0"
    (area "1" stays cached). Returns per-stage steady attribution plus
    the derived `oroutes_share`: the fraction of the full-table budget
    (routes × steady rounds) merge + redistribute actually touched —
    ~1 while those walks were O(routes) (BENCH_WORK.json pinned ratios
    6565/13129), ~0 since the delta books (BENCH_WORK_r02.json).
    """
    from openr_tpu.common import constants as C
    from openr_tpu.config import AreaConfig, Config, NodeConfig
    from openr_tpu.fib.fib import Fib, MockFibHandler
    from openr_tpu.monitor import Counters, compile_ledger, work_ledger
    from openr_tpu.types.kvstore import Publication, Value
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.serde import to_wire
    from openr_tpu.types.topology import PrefixDatabase, PrefixEntry
    from openr_tpu.utils import topogen

    led = compile_ledger.install()
    work_ledger.reset()
    areas = ("0", "1")
    if mode == "topo":
        side = max(2, int(round(nodes ** 0.5)))
        adj_dbs, prefix_dbs = topogen.grid(side, side)
    else:
        k = max(4, int(round((nodes * 4 / 5) ** 0.5 / 2)) * 2)
        adj_dbs, prefix_dbs = topogen.fat_tree(k, metric=10)
    counters = Counters()
    dec, _pubs, routes, pub_for = build_decision(
        adj_dbs, prefix_dbs, solver=solver, counters=counters, areas=areas
    )
    if solver == "tpu" and dec._tpu is not None:
        # the native single-root engine has no warm-start path (see
        # measure_topo_churn): measure the batched-kernel pipeline so
        # topo rounds take the warm path, not a full solve per flap
        dec._tpu.native_rib = "off"
    names = [db.this_node_name for db in adj_dbs]
    root = names[0]

    # pad the prefix table to the target scale, split between the two
    # areas (so each per-area RIB holds ~half and the merge fold is the
    # only place the full table exists). Batched publications: one
    # process_publication per 2048 keys, not per prefix.
    batches: dict[str, dict] = {a: {} for a in areas}

    def flush(area: str) -> None:
        if batches[area]:
            dec.process_publication(
                Publication(area=area, key_vals=dict(batches[area]))
            )
            batches[area].clear()

    for i in range(max(0, prefixes - len(dec.rib.unicast_routes))):
        node = names[i % len(names)]
        area = areas[i % 2]
        pstr = f"10.{128 + (i >> 16)}.{(i >> 8) & 0xFF}.{i & 0xFF}/32"
        batches[area][C.prefix_key(node, area, pstr)] = Value(
            version=1,
            originator_id=node,
            value=to_wire(
                PrefixDatabase(
                    this_node_name=node,
                    prefix_entries=(
                        PrefixEntry(prefix=IpPrefix(prefix=pstr)),
                    ),
                    area=area,
                )
            ),
        ).with_hash()
        if len(batches[area]) >= 2048:
            flush(area)
    for area in areas:
        flush(area)

    two_area_cfg = Config(
        NodeConfig(
            node_name=root,
            areas=tuple(AreaConfig(area_id=a) for a in areas),
        )
    )
    from openr_tpu.prefixmgr.prefix_manager import PrefixManager

    pm = PrefixManager(two_area_cfg, _NullKv(), counters=counters)
    fib = Fib(
        two_area_cfg,
        routes.get_reader("work_fib"),
        MockFibHandler(),
        counters=counters,
    )
    reader = routes.get_reader("work_bench")

    rng = np.random.default_rng(seed)
    pool_n = 256
    advertised = [False] * pool_n
    versions: dict[str, int] = {}
    adj_cur = {db.this_node_name: db for db in adj_dbs}
    adj_versions = {n: 1 for n in names}

    def churn_prefix_round():
        for _ in range(burst):
            i = int(rng.integers(0, pool_n))
            node = names[i % len(names)]
            pstr = f"10.77.{i >> 8}.{i & 0xFF}/32"
            key = C.prefix_key(node, "0", pstr)
            if advertised[i]:
                pub = Publication(area="0", expired_keys=[key])
            else:
                versions[key] = versions.get(key, 0) + 1
                pub = Publication(
                    area="0",
                    key_vals={
                        key: Value(
                            version=versions[key],
                            originator_id=node,
                            value=to_wire(
                                PrefixDatabase(
                                    this_node_name=node,
                                    prefix_entries=(
                                        PrefixEntry(
                                            prefix=IpPrefix(prefix=pstr)
                                        ),
                                    ),
                                    area="0",
                                )
                            ),
                        ).with_hash()
                    },
                )
            advertised[i] = not advertised[i]
            dec.process_publication(pub)

    def churn_topo_round():
        import dataclasses

        node = names[int(rng.integers(1, len(names)))]
        db = adj_cur[node]
        j = int(rng.integers(0, len(db.adjacencies)))
        old = int(db.adjacencies[j].metric)
        new = old
        while new == old:
            new = int(rng.integers(1, 64))
        adjs = list(db.adjacencies)
        adjs[j] = dataclasses.replace(adjs[j], metric=new)
        db = dataclasses.replace(db, adjacencies=tuple(adjs))
        adj_cur[node] = db
        adj_versions[node] += 1
        dec.process_publication(
            pub_for(db, version=adj_versions[node], area="0")
        )

    async def feed_downstream() -> None:
        """Run every drained RouteUpdate through the real downstream
        consumers — the Fib delta program and the ABR redistribution
        fold — exactly as their module loops would."""
        while True:
            upd = reader.get_nowait()
            if upd is None:
                return
            fib._fold_update(upd)
            fib._have_rib = True
            await fib._program_once()
            pm.fold_rib_update(upd)
            pm._sync_advertisements()

    async def run():
        samples: list[float] = []
        await dec._rebuild_routes()  # initial full build (jit compile)
        await feed_downstream()  # initial FULL_SYNC program + fold
        for r in range(rounds):
            if r == warmup_rounds:
                led.mark_warm()
                work_ledger.mark_warm()
            if mode == "topo":
                churn_topo_round()
            else:
                churn_prefix_round()
            await dec._rebuild_routes()
            await feed_downstream()
            if r >= warmup_rounds:
                samples.append(dec._last_spf_ms)
        return samples

    with _bench_trace():
        samples = asyncio.new_event_loop().run_until_complete(run())
    steady_compiles = led.compiles_since_warm()
    led.reset_warm()
    work = work_ledger.since_warm()
    # the delta-proportional-by-design stages must hold k·delta+floor —
    # since ISSUE 17 that includes merge and redistribute (delta merge
    # book + incremental redistribution books). Full area solves, the
    # fallback merge_full fold and the warm region (topology-bounded,
    # not delta-count-bounded) are the documented exemptions
    # (docs/Monitor.md "Work ledger"). Under topology dirt the route-db
    # diff is also honestly O(tables) — a metric change can move any
    # route, so both tables are compared — while under prefix churn it
    # is scoped (ratio 1) and gated.
    exempt = ("spf_full", "spf_warm", "merge_full", "full_sync")
    if mode == "topo":
        exempt = exempt + ("diff",)
    violations = work_ledger.steady_violations(exempt=exempt)
    work_ledger.reset_warm()
    arr = np.array(samples) if samples else np.array([0.0])
    steady_rounds = max(1, rounds - warmup_rounds)
    oroutes_touched = sum(
        work.get(s, {}).get("touched", 0) for s in ("merge", "redistribute")
    )

    def stage_ratio(stage: str):
        row = work.get(stage)
        return row["ratio"] if row else None

    def touched_per_round(stage: str):
        row = work.get(stage)
        if not row or not row["rounds"]:
            return 0.0
        return round(row["touched"] / row["rounds"], 1)

    routes_total = len(dec.rib.unicast_routes) + len(dec.rib.mpls_routes)
    return {
        "work_churn_p50_ms": round(float(np.percentile(arr, 50)), 3),
        "work_churn_p99_ms": round(float(np.percentile(arr, 99)), 3),
        "mode": mode,
        "nodes": len(adj_dbs),
        "prefixes": prefixes,
        "routes_total": routes_total,
        "redistribution_book": len(pm._entries),
        "rounds": rounds,
        "steady_rounds": steady_rounds,
        "burst": burst,
        "engine": solver,
        "steady_state_compiles": sum(steady_compiles.values()),
        "steady_state_compile_fns": sorted(steady_compiles),
        "work": work,
        # the headline attribution, re-based by ISSUE 17: the fraction
        # of the full-table budget (routes_total × steady rounds) that
        # merge + redistribute actually touched. ~1 while the walks
        # were O(routes); ~0 now that both stages are delta-native.
        # (The old all-stages-touched denominator stopped meaning
        # anything once every stage became delta-proportional — the
        # two stages' RELATIVE share among tiny per-delta costs is not
        # the regression signal; their absolute table share is.)
        "oroutes_share": round(
            oroutes_touched / max(routes_total * steady_rounds, 1), 4
        ),
        "merge_touched_per_round": touched_per_round("merge"),
        "redistribute_touched_per_round": touched_per_round("redistribute"),
        "work_merge_ratio": stage_ratio("merge"),
        "work_redistribute_ratio": stage_ratio("redistribute"),
        "work_election_ratio": stage_ratio("election"),
        "work_fib_ratio": stage_ratio("fib"),
        "work_dirt_ratio": stage_ratio("dirt"),
        "work_violations": violations,
        "rebuild_prefix_only": int(
            counters.get("decision.rebuild.prefix_only")
        ),
        "rebuild_topo_delta": int(
            counters.get("decision.rebuild.topo_delta")
        ),
        "rebuild_full": int(counters.get("decision.rebuild.full")),
    }


def _ledger_round_cost_us(iters: int = 100_000) -> float:
    """Deterministic microbench of ONE prefix-churn round's ledger
    traffic — the exact commit/scope sites a scoped rebuild performs
    (dirt commit, election scope, assembly commit, diff commit; merge
    only joins in multi-area). Isolated on a private WorkLedger so the
    measurement never pollutes the process ledger."""
    import time as _time

    from openr_tpu.monitor.work_ledger import WorkLedger

    led = WorkLedger()
    led.mark_warm()  # worst case: the warm path also tracks worst-round
    t0 = _time.perf_counter()
    for _ in range(iters):
        led.commit("dirt", 2, 2)
        with led.scope("election", 2) as ws:
            ws.add(3)
        led.commit("assembly", 2, 2)
        led.commit("diff", 2, 2)
    return (_time.perf_counter() - t0) / iters * 1e6


def measure_work_overhead(
    nodes: int = 80, rounds: int = 400, repeats: int = 3
) -> dict:
    """WorkScope steady-state cost on the hottest measured path,
    reported two ways:

      * headline `overhead_pct` — the deterministic per-round ledger
        cost (`_ledger_round_cost_us`) as a percentage of the measured
        enabled-arm prefix-churn p50. The ledger does a handful of
        integer commits per round (~4 µs), which is below what
        end-to-end timing can resolve on a burstable host, so the
        exact code-path cost is the honest headline.
      * `e2e_paired_pct` — prefix-churn p50 with accounting ON vs OFF
        (`work_ledger.set_enabled`), interleaved pairs, median of
        per-pair ratios (adjacent pairs share the host's slow drift).
        Corroboration only: across runs it lands within ±several
        percent of zero, i.e. indistinguishable from no overhead —
        which is the point, and why it is not the gate.
    """
    on: list[float] = []
    off: list[float] = []
    for _ in range(max(1, repeats)):
        off.append(
            measure_prefix_churn(
                nodes=nodes, rounds=rounds, solver="tpu",
                work_accounting=False,
            )["prefix_churn_p50_ms"]
        )
        on.append(
            measure_prefix_churn(
                nodes=nodes, rounds=rounds, solver="tpu",
                work_accounting=True,
            )["prefix_churn_p50_ms"]
        )
    pair_pcts = sorted(
        (a / max(b, 1e-9) - 1) * 100 for a, b in zip(on, off)
    )
    e2e_paired_pct = pair_pcts[len(pair_pcts) // 2]
    round_us = _ledger_round_cost_us()
    p50_us = min(on) * 1e3
    return {
        "overhead_pct": round(round_us / max(p50_us, 1e-9) * 100, 2),
        "ledger_us_per_round": round(round_us, 3),
        "e2e_paired_pct": round(e2e_paired_pct, 2),
        "e2e_pair_pcts": [round(p, 2) for p in pair_pcts],
        "p50_ms_enabled": min(on),
        "p50_ms_disabled": min(off),
        "p50_ms_enabled_runs": on,
        "p50_ms_disabled_runs": off,
        "repeats": repeats,
    }


def _grid_edges(side: int) -> list[tuple[str, str]]:
    edges = []
    for r in range(side):
        for c in range(side):
            if c < side - 1:
                edges.append((f"n{r}x{c}", f"n{r}x{c + 1}"))
            if r < side - 1:
                edges.append((f"n{r}x{c}", f"n{r + 1}x{c}"))
    return edges


async def _new_traces(cluster, seen_before: dict[str, int], timeout_s: float):
    """Wait for the first node to complete a new PerfEvents trace after
    a link event, then keep collecting until the count is stable for a
    full second (drain, not a fixed grace window: a fixed window
    censors exactly the slow stragglers a slow codec produces, biasing
    its p50 LOW — the straggler set must close before either codec's
    distribution is read)."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s

    def collect():
        out = []
        for name, node in cluster.nodes.items():
            n_new = (
                int(node.counters.get("monitor.perf_traces", 0))
                - seen_before[name]
            )
            if n_new > 0:
                out.extend(list(node.monitor.perf_traces)[-n_new:])
        return out

    while loop.time() < deadline:
        if collect():
            break
        await asyncio.sleep(0.05)
    stable_since = loop.time()
    n_last = len(collect())
    while loop.time() < deadline:
        await asyncio.sleep(0.1)
        n_now = len(collect())
        if n_now != n_last:
            n_last = n_now
            stable_since = loop.time()
        elif loop.time() - stable_since >= 1.0:
            break
    return collect()


# counters the flood bench reports as deltas (all summed cluster-wide)
_FLOOD_COUNTERS = (
    "kvstore.floods_sent",
    "kvstore.flood_bytes",
    "kvstore.flood_span_bytes",
    "kvstore.flood_encodes",
    "kvstore.flood_keys_coalesced",
    "kvstore.full_syncs",
    "kvstore.full_syncs_served",
    "kvstore.full_sync_keys_sent",
    "kvstore.full_syncs_noop",
    "kvstore.full_syncs_noop_served",
    "kvstore.full_sync_probe_miss",
)


def measure_flood(
    codec: str = "bin",
    side: int = 8,
    churn_events: int = 400,
    churn_hz: float = 200.0,
    pool: int = 48,
    flap_rounds: int = 4,
    seed: int = 11,
    timeout_s: float = 180.0,
    trace_every: int = 0,
) -> dict:
    """Full-stack emulated-cluster flood benchmark for ONE wire codec
    (`--flood-bench` runs it for both and prints the comparison).

    A side×side grid of complete OpenrNodes (real Spark / LinkMonitor /
    KvStore / Decision / Fib over mock I/O, CPU oracle solver — no jax)
    runs three seeded stages:

      1. sustained prefix churn through the PrefixManager seam at
         `churn_hz`, then drain until every store is byte-identical —
         floods/sec (deliveries per second of pure-CPU wire-seam
         time: `kvstore.flood_encode_ms` + `flood_decode_ms`) and
         bytes/flood
         over that window, all counter-derived (`kvstore.flood_bytes`
         is the wire frame size the transport reported, not an
         estimate; wall-clock floods/sec is reported as
         `floods_per_sec_wall` but is pipeline- and host-noise-
         dominated);
      2. `flap_rounds` link fail/heal events — `convergence_p50_ms`
         from the PerfEvents traces (NEIGHBOR_EVENT → FIB_PROGRAMMED),
         the same instrumentation bench.py's headline uses;
      3. one forced anti-entropy sweep on the converged cluster — the
         delta full_sync path's noop-probe counters (docs/Wire.md).

    Ends with the emulator invariant checker (same classes the chaos
    and soak suites gate on) so the measured path is also a verified
    one. The serialize-once contract is visible in the row:
    `encodes_per_flood` ≈ 1/fan-out on the binary path, exactly 1.0 on
    the legacy per-peer JSON path.
    """
    import random
    from dataclasses import replace

    from openr_tpu.emulator import invariants
    from openr_tpu.emulator.cluster import Cluster, scaled_spark
    from openr_tpu.monitor import perf
    from openr_tpu.prefixmgr.prefix_manager import (
        PrefixEvent,
        PrefixEventType,
        PrefixSource,
    )
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import PrefixEntry

    n_nodes = side * side
    # the bench CHURNS while the whole grid shares one host core:
    # scale the Spark timers as if the cluster were 2x its size, or
    # the 64-node JSON baseline bring-up wave hits the hold-expiry
    # flap storm scaled_spark's docstring describes (the hold timer
    # would be measuring codec cost, not liveness — exactly the
    # congestion this PR's binary path relieves). The hold timer is
    # then pinned well past the worst event-loop stall a churn-drain
    # wave produces (the JSON baseline stalls keepalive RX for
    # multiple seconds at 64 nodes; a hold inside that window turns
    # the drain into a self-sustaining neighbor-down cascade) but
    # below _new_traces' 30 s flap-detection window. Trace-derived
    # convergence starts at NEIGHBOR_EVENT, so the longer hold never
    # enters the reported latency — it only delays fail_link
    # detection. Key TTL is pushed past the bench horizon: the
    # default 300s TTL starts synchronized refresh waves ~225s in
    # (client.py TTL_REFRESH_FRACTION), background noise that would
    # pollute the seeded workload both codecs must share.
    spark_hdr = scaled_spark(n_nodes * 2) if n_nodes > 16 else None
    if spark_hdr is not None:
        spark_hdr = replace(
            spark_hdr,
            hold_time_ms=12_000,
            graceful_restart_time_ms=24_000,
        )

    def transform(ncfg):
        if spark_hdr is not None:
            ncfg = replace(
                ncfg,
                spark=replace(
                    spark_hdr, wire_codec=ncfg.spark.wire_codec
                ),
            )
        return replace(
            ncfg,
            kvstore=replace(
                ncfg.kvstore,
                key_ttl_ms=3_600_000,
                # cross-node flood tracing (docs/Monitor.md): sampled
                # hop spans ride the floods; 0 = tracing off (the
                # baseline the --flood-trace overhead gate compares to)
                trace_sample_every=trace_every,
                trace_seed=seed,
            ),
        )

    c = Cluster.from_edges(
        _grid_edges(side), solver="cpu", wire_codec=codec,
        node_config_transform=transform,
    )

    def csum(name: str) -> int:
        return sum(
            int(n.counters.get(name, 0)) for n in c.nodes.values()
        )

    def snap() -> dict[str, int]:
        return {k: csum(k) for k in _FLOOD_COUNTERS}

    def seam_split() -> dict[str, float]:
        """Cluster-wide pure-CPU time inside the wire seam, split by
        side: every flood encode (`kvstore.flood_encode_ms`) and every
        receive decode (`kvstore.flood_decode_ms`). Neither stat spans
        an await, so event-loop queueing — which dominates the
        wall-clock `kvstore.flood_fanout_ms` latency under a 64-node
        churn wave and drowns the codec effect in scheduler noise —
        can't inflate it (docs/Wire.md)."""
        out = {"enc": 0.0, "dec": 0.0}
        for n in c.nodes.values():
            for key, stat in (
                ("enc", "kvstore.flood_encode_ms"),
                ("dec", "kvstore.flood_decode_ms"),
            ):
                s = n.counters.stats.get(stat)
                if s is not None:
                    out[key] += s.sum
        return out

    def seam_ms_sum() -> float:
        s = seam_split()
        return s["enc"] + s["dec"]

    ids: dict[str, int] = {}

    def push_prefix(node_name: str, idx: int, add: bool) -> None:
        entry = PrefixEntry(
            prefix=IpPrefix.make(
                f"10.210.{ids[node_name] & 0xFF}.{idx}/32"
            )
        )
        c.nodes[node_name].prefix_events.push(
            PrefixEvent(
                type=(
                    PrefixEventType.ADD_PREFIXES
                    if add
                    else PrefixEventType.WITHDRAW_PREFIXES
                ),
                source=PrefixSource.API,
                entries=(entry,),
            )
        )

    t_wall = time.perf_counter()

    def _stage(msg: str) -> None:
        print(
            f"[flood-bench {codec}] +{time.perf_counter() - t_wall:.1f}s "
            f"{msg}",
            file=sys.stderr,
        )

    async def run() -> dict:
        rng = random.Random(seed)
        await c.start()
        try:
            await c.wait_converged(timeout=timeout_s)
            _stage("converged")
            await asyncio.sleep(0.5)  # bring-up floods/syncs settle
            names = sorted(c.nodes)
            ids.update({n: i for i, n in enumerate(names)})
            loop = asyncio.get_running_loop()

            # stage 1: seeded prefix churn → counter-derived throughput
            base = snap()
            split0 = seam_split()
            advertised: set[tuple[str, int]] = set()
            t0 = loop.time()
            for _ in range(churn_events):
                node_name = names[rng.randrange(len(names))]
                idx = rng.randrange(pool)
                key = (node_name, idx)
                add = key not in advertised
                push_prefix(node_name, idx, add)
                (advertised.add if add else advertised.discard)(key)
                await asyncio.sleep(1.0 / churn_hz)
            _stage(f"churn pushed ({loop.time() - t0:.1f}s)")
            while True:
                # drained = routes converged AND every store identical
                if c.converged() and not invariants.check_kvstore_consistency(c):
                    break
                if loop.time() - t0 > timeout_s:
                    raise TimeoutError("flood churn never drained")
                await asyncio.sleep(0.05)
            elapsed = loop.time() - t0
            churn = {k: csum(k) - base[k] for k in _FLOOD_COUNTERS}
            split1 = seam_split()
            seam_enc = split1["enc"] - split0["enc"]
            seam_dec = split1["dec"] - split0["dec"]
            seam_ms = seam_enc + seam_dec
            _stage(f"churn drained ({elapsed:.1f}s)")

            # stage 2: link flaps → trace-derived convergence latency
            trace_ms: list[float] = []
            for _ in range(flap_rounds):
                ls = c.links[rng.randrange(len(c.links))]
                seen = {
                    name: int(
                        node.counters.get("monitor.perf_traces", 0)
                    )
                    for name, node in c.nodes.items()
                }
                c.fail_link(ls.a, ls.b)
                got = await _new_traces(c, seen, timeout_s=30.0)
                trace_ms.extend(
                    t.total_ms()
                    for t in got
                    if t.last_event() == perf.FIB_PROGRAMMED
                    and len(t.events) >= 5
                )
                c.heal_link(ls.a, ls.b)
                await c.wait_converged(timeout=timeout_s)
                await asyncio.sleep(0.3)

            _stage("flap stage done")
            # stage 3: forced anti-entropy sweep on the converged
            # cluster — the delta full_sync noop-probe fast path
            base_ae = snap()
            for node in c.nodes.values():
                await node.kvstore._anti_entropy()
            t_ae = loop.time()
            while any(
                p.sync_task is not None and not p.sync_task.done()
                for node in c.nodes.values()
                for p in node.kvstore.peers.values()
            ):
                if loop.time() - t_ae > timeout_s:
                    raise TimeoutError("anti-entropy sweep stuck")
                await asyncio.sleep(0.02)
            ae = {k: csum(k) - base_ae[k] for k in _FLOOD_COUNTERS}
            _stage("anti-entropy swept")

            # the measured path must also be a correct one: same
            # invariant classes + quiescence gate the chaos and soak
            # suites end every round with
            await invariants.wait_quiescent(
                c,
                timeout_s=timeout_s,
                context=f"flood-bench codec={codec} seed={seed}",
            )
            _stage("quiesced")

            floods = churn["kvstore.floods_sent"]
            tarr = np.array(trace_ms) if trace_ms else np.array([0.0])
            trace_stats = None
            if trace_every > 0:
                # completed hop-span traces cluster-wide: completions,
                # deepest path, waterfall-vs-total agreement, and the
                # per-stage attribution the BENCH row carries
                from openr_tpu.emulator import tracing

                trace_stats = tracing.trace_report(c)
            return {
                "codec": codec,
                "nodes": len(c.nodes),
                "churn_events": churn_events,
                "churn_elapsed_s": round(elapsed, 2),
                "floods_sent": floods,
                # the headline throughput: deliveries per second of
                # wire-SEAM time (counter-derived from the pure-CPU
                # kvstore.flood_encode_ms + flood_decode_ms stats —
                # see seam_ms_sum). The wall-clock variant is
                # kept for context but is dominated by the rest of
                # the pipeline (decision rebuilds, fib programming)
                # and by this host class's sustained-load throttling
                # (±25% between adjacent identical runs) — it cannot
                # resolve a wire-path change; the seam measure can
                # (docs/Wire.md)
                "floods_per_sec": round(
                    floods / max(seam_ms / 1e3, 1e-9), 1
                ),
                "wire_seam_ms": round(seam_ms, 1),
                "wire_seam_encode_ms": round(seam_enc, 1),
                "wire_seam_decode_ms": round(seam_dec, 1),
                # codec efficiency, robust to coalescing batch shape:
                # µs/flood conflates batch size with codec cost (bigger
                # batches = fewer, fatter frames), ns/byte does not
                "seam_ns_per_byte": round(
                    seam_ms * 1e6
                    / max(churn["kvstore.flood_bytes"], 1),
                    2,
                ),
                # flood tracing's DIRECT wire footprint: packed span
                # bytes shipped as a fraction of all flood bytes
                "span_byte_share": round(
                    churn["kvstore.flood_span_bytes"]
                    / max(churn["kvstore.flood_bytes"], 1),
                    5,
                ),
                "floods_per_sec_wall": round(floods / elapsed, 1),
                "flood_bytes": churn["kvstore.flood_bytes"],
                "bytes_per_flood": round(
                    churn["kvstore.flood_bytes"] / max(floods, 1), 1
                ),
                "flood_encodes": churn["kvstore.flood_encodes"],
                "encodes_per_flood": round(
                    churn["kvstore.flood_encodes"] / max(floods, 1), 3
                ),
                "keys_coalesced": churn["kvstore.flood_keys_coalesced"],
                "convergence_p50_ms": round(
                    float(np.percentile(tarr, 50)), 3
                ),
                "convergence_p99_ms": round(
                    float(np.percentile(tarr, 99)), 3
                ),
                "convergence_traces": len(trace_ms),
                "anti_entropy": {
                    "full_syncs": ae["kvstore.full_syncs"],
                    "noop": ae["kvstore.full_syncs_noop"],
                    "noop_served": ae["kvstore.full_syncs_noop_served"],
                    "probe_miss": ae["kvstore.full_sync_probe_miss"],
                    "keys_sent": ae["kvstore.full_sync_keys_sent"],
                },
                "trace_every": trace_every,
                "flood_traces": trace_stats,
                # per-stage p50 breakdown from hop spans (alongside
                # convergence_p50_ms, per the observability plan)
                "convergence_attribution": (
                    trace_stats["attribution"].get("stages_p50_ms")
                    if trace_stats is not None
                    else None
                ),
                "invariants": "ok",
            }
        finally:
            await c.stop()

    # OPENR_BENCH_TRACE=<dir> wraps the whole flood run (churn + flap +
    # anti-entropy stages) in an xprof trace
    with _bench_trace():
        return asyncio.run(run())


def _smoke_gate(label: str, scoped: dict, checks: dict[str, bool]) -> None:
    """Shared CI-gate core for the churn smoke lanes: every named check
    must hold, plus the clause common to EVERY lane — zero post-warmup
    XLA compiles (the compile-ledger invariant; a steady-state recompile
    means a shape leaked past the padding buckets, docs/Linting.md
    OR008-OR010). On failure: one diagnostic line naming the failed
    checks with the full counter row, then exit 1."""
    checks = dict(checks)
    checks["zero steady-state compiles"] = (
        scoped["steady_state_compiles"] == 0
    )
    failed = [name for name, ok in checks.items() if not ok]
    if not failed:
        return
    counters = {
        k: v for k, v in scoped.items() if not k.endswith("_ms")
    }
    print(
        f"{label} smoke FAILED: {'; '.join(failed)} — "
        f"counters: {json.dumps(counters)}",
        file=sys.stderr,
    )
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1280)
    ap.add_argument("--flaps-per-sec", type=float, default=1000.0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--debounce-min-ms", type=float, default=None)
    ap.add_argument("--debounce-max-ms", type=float, default=None)
    ap.add_argument("--burst", type=int, default=10)
    ap.add_argument(
        "--backend", choices=("auto", "cpu"), default="auto",
        help="cpu forces jax onto the host CPU (sets JAX_PLATFORMS=cpu "
        "before jax is imported); auto takes jax's default backend",
    )
    ap.add_argument(
        "--prefix-churn", action="store_true",
        help="run the prefix-only (re-advertise/withdraw) workload on a "
        "fixed topology instead of link flaps: measures the dirty-scoped "
        "rebuild fast path, and the same workload forced down the "
        "full-rebuild path for the speedup ratio",
    )
    ap.add_argument("--prefix-rounds", type=int, default=120)
    ap.add_argument(
        "--force-full", action="store_true",
        help="with --prefix-churn/--topo-churn: skip the scoped/warm "
        "run and measure only the forced full-rebuild path",
    )
    ap.add_argument(
        "--topo-churn", action="store_true",
        help="run the seeded link-flap + metric-change storm on a fixed "
        "grid: measures the topology-delta warm-start path "
        "(decision.rebuild.topo_delta), and the same workload forced "
        "down the full path for the speedup ratio",
    )
    ap.add_argument("--topo-rounds", type=int, default=60)
    ap.add_argument(
        "--flood-bench", action="store_true",
        help="run the full-stack emulated-cluster flood benchmark on "
        "BOTH wire codecs (legacy per-peer JSON vs serialize-once "
        "binary, docs/Wire.md): floods/sec, counter-derived "
        "bytes/flood, trace-derived convergence_p50_ms, and the delta "
        "full_sync noop-probe counters, with the emulator invariant "
        "checker gating each run",
    )
    ap.add_argument(
        "--flood-side", type=int, default=8,
        help="grid side for --flood-bench (8 → the 64-node headline)",
    )
    ap.add_argument("--flood-events", type=int, default=400)
    ap.add_argument("--flood-flaps", type=int, default=4)
    ap.add_argument(
        "--flood-codec", choices=("both", "bin", "json"), default="both",
    )
    ap.add_argument(
        "--flood-timeout", type=float, default=180.0,
        help="per-stage timeout (s) inside each flood-bench run; the "
        "64-node JSON baseline on a throttled burstable host can need "
        "several minutes to drain — raise this rather than letting "
        "the slow BASELINE abort the comparison",
    )
    ap.add_argument(
        "--flood-trace", action="store_true",
        help="run the flood workload in interleaved traced/untraced "
        "pairs on the binary codec (--flood-trace-every sampling, "
        "--flood-repeats pairs) and report completed cross-node "
        "traces, the named-stage waterfall/attribution, and tracing's "
        "isolated wire cost (span byte share + seam ns/byte ratio). "
        "With --smoke, exits 1 unless sampled traces complete "
        "end-to-end across >=3 hops, waterfalls attribute >=95%% of "
        "each span's total, and both overhead estimators stay <5%% "
        "(docs/Monitor.md 'Flood tracing')",
    )
    ap.add_argument(
        "--flood-trace-every", type=int, default=8,
        help="head-sampling period for the traced --flood-trace run "
        "(every Nth origination per node, seeded; the ci lane passes "
        "16 — sparser sampling trades span count for a wider margin "
        "under the 5%% overhead gate)",
    )
    ap.add_argument(
        "--flood-repeats", type=int, default=1,
        help="interleaved json/bin measurement rounds; each reported "
        "comparison scalar is the per-metric median across rounds "
        "(counters the throttled-host drift that penalizes whichever "
        "codec runs last, without coupling noisy metrics to one run)",
    )
    ap.add_argument(
        "--work-bench", action="store_true",
        help="run the work-ledger attribution bench (docs/Monitor.md "
        "'Work ledger'): the full dataflow — two-area decision, real "
        "Fib delta programming, real ABR PrefixManager redistribution "
        "— under prefix AND topo churn, reporting per-stage "
        "touched-entity attribution, merge + redistribute's share of "
        "the full-table budget (oroutes_share, ~0 since the ISSUE 17 "
        "delta books), and (without --smoke) the WorkScope overhead "
        "measurement. With --smoke: exits 1 unless work.election.ratio "
        "and work.fib.ratio hold their bounds, merge/redistribute "
        "ratios stay delta-proportional (<= 8), oroutes_share ~0, zero "
        "post-warmup XLA compiles landed, and no delta-proportional "
        "stage violated k*delta+floor",
    )
    ap.add_argument("--work-prefixes", type=int, default=100_000)
    ap.add_argument("--work-rounds", type=int, default=24)
    ap.add_argument("--work-burst", type=int, default=16)
    ap.add_argument(
        "--work-mode", choices=("both", "prefix", "topo"), default="both",
    )
    ap.add_argument(
        "--work-overhead-repeats", type=int, default=3,
        help="interleaved on/off pairs for the WorkScope overhead "
        "measurement (0 skips it; --smoke always skips it)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI gate mode. With --topo-churn: byte-parity checked "
        "against from-scratch compute_rib every few rounds, and the "
        "process exits 1 unless the warm-start path was actually taken "
        "(counter-asserted) and parity held. With --prefix-churn: the "
        "scoped path must run zero SPF solves. Both paths additionally "
        "assert ZERO post-warmup XLA compiles via the runtime compile "
        "ledger (monitor/compile_ledger.py) — a steady-state recompile "
        "means a shape leaked past the padding buckets",
    )
    args = ap.parse_args()
    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    if args.flood_trace:
        kw = dict(
            side=args.flood_side,
            churn_events=args.flood_events,
            flap_rounds=args.flood_flaps,
            timeout_s=args.flood_timeout,
        )
        # interleaved (baseline, traced) pairs — the PR 8 lesson: this
        # host class drifts between adjacent runs, and the workload
        # itself is timing-coupled (coalescing batch shapes shift run
        # to run), so single-pair comparisons swing tens of percent.
        # Tracing overhead is therefore measured by two estimators
        # that ISOLATE the tracing cost instead of the batch shape:
        #   * span_byte_share — packed span bytes as a fraction of all
        #     flood bytes (the direct wire footprint; counter-derived);
        #   * seam ns/byte ratio, min-per-arm — codec efficiency per
        #     byte (the seam stat is pure CPU, so contention and
        #     unlucky draws only ever add time; µs-per-FLOOD is
        #     reported but NOT gated: span bookkeeping slows relays a
        #     hair, the pump then coalesces MORE keys per frame, and
        #     per-flood time rises while per-byte cost falls — a batch
        #     shape change, not a tracing cost).
        pairs = max(1, args.flood_repeats)
        runs_b: list[dict] = []
        runs_t: list[dict] = []
        for _ in range(pairs):
            runs_b.append(measure_flood("bin", **kw))
            runs_t.append(
                measure_flood(
                    "bin",
                    trace_every=max(1, args.flood_trace_every),
                    **kw,
                )
            )

        def seam_us_per_flood(r: dict) -> float:
            return r["wire_seam_ms"] * 1e3 / max(r["floods_sent"], 1)

        base_nsb = min(r["seam_ns_per_byte"] for r in runs_b)
        traced_nsb = min(r["seam_ns_per_byte"] for r in runs_t)
        per_byte_pct = round((traced_nsb / base_nsb - 1.0) * 100, 2)
        span_shares = [
            round(r["span_byte_share"] * 100, 2) for r in runs_t
        ]
        span_share_pct = max(span_shares)
        # headline: the larger of the two isolated costs (per-byte
        # processing degradation, added span bytes)
        overhead_pct = max(per_byte_pct, span_share_pct)
        reports = [r["flood_traces"] or {} for r in runs_t]
        attrs = [ts.get("attribution") or {} for ts in reports]
        traced = runs_t[-1]
        detail = {
            "pairs": pairs,
            "baseline": runs_b[-1],
            "traced": traced,
            "seam_per_byte_overhead_pct": per_byte_pct,
            "span_byte_share_pct": span_share_pct,
            "span_byte_share_runs_pct": span_shares,
            "seam_ns_per_byte_baseline_runs": [
                r["seam_ns_per_byte"] for r in runs_b
            ],
            "seam_ns_per_byte_traced_runs": [
                r["seam_ns_per_byte"] for r in runs_t
            ],
            "seam_us_per_flood_baseline_runs": [
                round(seam_us_per_flood(r), 2) for r in runs_b
            ],
            "seam_us_per_flood_traced_runs": [
                round(seam_us_per_flood(r), 2) for r in runs_t
            ],
            "trace_every": traced["trace_every"],
            # quality gates aggregate conservatively across traced
            # runs: completions/hops must be reached in EVERY run is
            # too strict for a smoke (draws differ) — best-of for
            # reach, worst-of for correctness fractions
            "completions": max(
                (ts.get("completions", 0) for ts in reports), default=0
            ),
            "max_hops": max(
                (ts.get("max_hops", 0) for ts in reports), default=0
            ),
            "waterfall_ok_frac": min(
                (ts.get("waterfall_ok_frac") or 0 for ts in reports),
                default=0,
            ),
            "attribution_coverage_p50": min(
                (a.get("coverage_p50") or 0 for a in attrs), default=0
            ),
            "convergence_attribution": traced.get(
                "convergence_attribution"
            ),
            "overhead_pct": overhead_pct,
        }
        print(
            json.dumps(
                {
                    "metric": "flood_trace_overhead_pct",
                    "value": overhead_pct,
                    "unit": "%",
                    "vs_baseline": None,
                    "detail": detail,
                }
            )
        )
        if args.smoke:
            checks = {
                # traces actually flowed and completed cluster-wide
                "traces completed (>=50)": detail["completions"] >= 50,
                # at least one span crossed >=3 flooding hops end-to-end
                ">=3-hop trace completed": detail["max_hops"] >= 3,
                # named stages telescope to the span total: every
                # waterfall within 5% of its trace's total_ms, p50
                # coverage >=95% (the acceptance's attribution bar) —
                # in EVERY traced run
                "waterfalls match totals": (
                    detail["waterfall_ok_frac"] >= 0.95
                    and detail["attribution_coverage_p50"] >= 0.95
                ),
                # sampled tracing's isolated wire cost <5%: per-byte
                # codec efficiency must not degrade AND the packed
                # spans' direct byte footprint must stay small
                "tracing overhead <5%": (
                    per_byte_pct < 5.0 and span_share_pct < 5.0
                ),
                "invariants clean": all(
                    r["invariants"] == "ok" for r in (*runs_b, *runs_t)
                ),
            }
            failed = [name for name, ok in checks.items() if not ok]
            if failed:
                print(
                    f"flood-trace smoke FAILED: {'; '.join(failed)} — "
                    f"detail: {json.dumps(detail)}",
                    file=sys.stderr,
                )
                sys.exit(1)
        return

    if args.flood_bench:
        kw = dict(
            side=args.flood_side,
            churn_events=args.flood_events,
            flap_rounds=args.flood_flaps,
            timeout_s=args.flood_timeout,
        )
        codecs = (
            ["json", "bin"]
            if args.flood_codec == "both"
            else [args.flood_codec]
        )
        # interleave codecs across repeats: this host's sustained-load
        # throttling (burstable CPU) makes LATER runs systematically
        # slower, so back-to-back per-codec runs would charge the drift
        # to whichever codec ran second — time-adjacent pairs + a
        # median per codec neutralize it
        samples: dict[str, list[dict]] = {c: [] for c in codecs}
        for _ in range(max(1, args.flood_repeats)):
            for codec_name in codecs:
                samples[codec_name].append(
                    measure_flood(codec_name, **kw)
                )
        def _median(vals: list[float]) -> float:
            vs = sorted(vals)
            n = len(vs)
            mid = vs[n // 2] if n % 2 else (vs[n // 2 - 1] + vs[n // 2]) / 2
            return round(mid, 3)

        # each comparison scalar is the PER-METRIC median across runs:
        # picking one "median row" (by any single metric) would couple
        # every other metric to that run's noise — convergence p50
        # especially swings ±50% round-to-round on this host class,
        # independently of which run had the median throughput
        _MEDIAN_KEYS = (
            "floods_per_sec", "wire_seam_ms", "floods_per_sec_wall",
            "bytes_per_flood", "encodes_per_flood", "churn_elapsed_s",
            "convergence_p50_ms", "convergence_p99_ms",
        )
        rows: dict[str, dict] = {}
        for codec_name, runs in samples.items():
            ordered = sorted(runs, key=lambda r: r["floods_per_sec"])
            med = dict(ordered[(len(ordered) - 1) // 2])
            if len(runs) > 1:
                for k in _MEDIAN_KEYS:
                    med[k] = _median([r[k] for r in runs])
                med["floods_per_sec_runs"] = [
                    r["floods_per_sec"] for r in runs
                ]
                med["convergence_p50_ms_runs"] = [
                    r["convergence_p50_ms"] for r in runs
                ]
            rows[codec_name] = med
        detail: dict = dict(rows)
        if len(rows) == 2:
            j, b = rows["json"], rows["bin"]
            detail["bytes_per_flood_ratio"] = round(
                j["bytes_per_flood"] / max(b["bytes_per_flood"], 1e-9), 2
            )
            detail["floods_per_sec_ratio"] = round(
                b["floods_per_sec"] / max(j["floods_per_sec"], 1e-9), 2
            )
            detail["convergence_p50_ratio"] = round(
                j["convergence_p50_ms"]
                / max(b["convergence_p50_ms"], 1e-9),
                2,
            )
        head = rows.get("bin") or rows["json"]
        print(
            json.dumps(
                {
                    "metric": "flood_throughput_per_sec",
                    "value": head["floods_per_sec"],
                    "unit": "floods/s",
                    "vs_baseline": None,
                    "detail": detail,
                }
            )
        )
        if args.smoke and len(rows) == 2:
            j, b = rows["json"], rows["bin"]
            checks = {
                # serialize-once actually engaged: strictly fewer
                # encodes than flood deliveries on the binary path,
                # while the legacy path pays one encode per delivery
                "binary path active": b["flood_encodes"] > 0
                and b["flood_encodes"] < b["floods_sent"],
                "delta full_sync served (noop probes)": (
                    b["anti_entropy"]["noop_served"] > 0
                    and b["anti_entropy"]["keys_sent"] == 0
                ),
                "floods/sec >= JSON baseline": (
                    b["floods_per_sec"] >= j["floods_per_sec"]
                ),
                "bytes/flood reduced >= 2x": (
                    b["bytes_per_flood"] * 2 <= j["bytes_per_flood"]
                ),
                # invariants: assert_invariants inside measure_flood
                # already raised on violation; this records the fact
                "invariants clean": all(
                    r["invariants"] == "ok" for r in rows.values()
                ),
            }
            failed = [name for name, ok in checks.items() if not ok]
            if failed:
                print(
                    f"flood-bench smoke FAILED: {'; '.join(failed)} — "
                    f"rows: {json.dumps(rows)}",
                    file=sys.stderr,
                )
                sys.exit(1)
        return

    if args.work_bench:
        modes = (
            ["prefix", "topo"]
            if args.work_mode == "both"
            else [args.work_mode]
        )
        rows: dict[str, dict] = {}
        for mode in modes:
            rows[mode] = measure_work_churn(
                nodes=args.nodes,
                prefixes=args.work_prefixes,
                rounds=args.work_rounds,
                burst=args.work_burst,
                mode=mode,
                solver="tpu",
            )
        overhead = None
        if not args.smoke and args.work_overhead_repeats > 0:
            overhead = measure_work_overhead(
                repeats=args.work_overhead_repeats
            )
        head = rows.get("prefix") or rows[modes[0]]
        row = {
            "metric": "work_oroutes_share",
            "value": head["oroutes_share"],
            "unit": "frac",
            "vs_baseline": None,
            # the per-stage ratios at TOP level so the bench-history
            # sentinel (benchmarks/history.py HEADLINE_METRICS) can
            # track their drift across runs
            "work_merge_ratio": head["work_merge_ratio"],
            "work_redistribute_ratio": head["work_redistribute_ratio"],
            "work_election_ratio": head["work_election_ratio"],
            "work_fib_ratio": head["work_fib_ratio"],
            "detail": {
                **rows,
                "work_overhead": overhead,
                "backend": _backend(),
            },
        }
        print(json.dumps(row))
        if not args.smoke:
            try:
                from benchmarks import history

                history.append_row(row)
            except Exception:  # noqa: BLE001 — read-only checkout etc.
                pass
        if args.smoke:
            for mode, scoped in rows.items():
                _smoke_gate(f"work-bench[{mode}]", scoped, {
                    # delta-proportional stages hold their pinned bounds
                    "fib ratio pinned at 1": (
                        scoped["work_fib_ratio"] is not None
                        and scoped["work_fib_ratio"] <= 1.5
                    ),
                    "election ratio bounded": (
                        scoped["work_election_ratio"] is None
                        or scoped["work_election_ratio"] <= 8.0
                    ),
                    # the two formerly-O(routes) walks are delta-native
                    # (ISSUE 17): ratios gate at a small constant (the
                    # merge fold touches scope × areas; redistribution
                    # touches the update's own churn) — a reintroduced
                    # full-table walk blows these by orders of magnitude
                    "merge ratio delta-proportional": (
                        scoped["work_merge_ratio"] is None
                        or scoped["work_merge_ratio"] <= 8.0
                    ),
                    "redistribute ratio delta-proportional": (
                        scoped["work_redistribute_ratio"] is None
                        or scoped["work_redistribute_ratio"] <= 8.0
                    ),
                    # merge + redistribute together touch ~none of the
                    # full-table budget under prefix churn; under topo
                    # churn a single flap legitimately reroutes a few
                    # percent of the table (the warm region's routes),
                    # so the bound is looser — still far below the ~1.0
                    # a reintroduced full-table walk would report
                    "oroutes share ~0": scoped["oroutes_share"] <= (
                        0.05 if mode == "prefix" else 0.25
                    ),
                    # the delta paths never retrace a kernel
                    "zero steady compiles": (
                        scoped["steady_state_compiles"] == 0
                    ),
                    # no scoped delta-proportional stage — merge and
                    # redistribute now included — breached k*delta+floor
                    # in any steady round
                    "no proportionality violations": (
                        not scoped["work_violations"]
                    ),
                })
        return

    if args.topo_churn:
        full = measure_topo_churn(
            nodes=args.nodes, rounds=max(10, args.topo_rounds // 3),
            solver="tpu", force_full=True,
        )
        scoped = None
        if not args.force_full:
            scoped = measure_topo_churn(
                nodes=args.nodes, rounds=args.topo_rounds, solver="tpu",
                check_parity_every=5 if args.smoke else 0,
            )
        head = scoped or full
        detail = {
            "warm": scoped,
            "forced_full": full,
            "backend": _backend(),
        }
        if scoped is not None:
            detail["speedup_vs_full"] = round(
                full["topo_churn_p50_ms"]
                / max(scoped["topo_churn_p50_ms"], 1e-6),
                1,
            )
        print(
            json.dumps(
                {
                    "metric": "topo_churn_p50_ms",
                    "value": head["topo_churn_p50_ms"],
                    "unit": "ms",
                    "vs_baseline": None,
                    "detail": detail,
                }
            )
        )
        if args.smoke and scoped is not None:
            # CI gate: the warm path must actually have been taken —
            # a single-link metric change must never pay a full
            # per-area solve — and byte-parity must hold
            _smoke_gate("topo-churn", scoped, {
                "parity": scoped["parity"] == "ok",
                "warm path every round": (
                    scoped["rebuild_topo_delta"] >= args.topo_rounds - 2
                ),
                "one initial full build": scoped["rebuild_full"] == 1,
                "warm starts taken": scoped["warm_starts"] > 0,
                "zero churn solves": scoped["churn_area_solves"] == 0,
            })
        return

    if args.prefix_churn:
        full = measure_prefix_churn(
            nodes=args.nodes, rounds=max(20, args.prefix_rounds // 3),
            solver="tpu", force_full=True,
        )
        scoped = None
        if not args.force_full:
            scoped = measure_prefix_churn(
                nodes=args.nodes, rounds=args.prefix_rounds, solver="tpu",
            )
        head = scoped or full
        detail = {
            "scoped": scoped,
            "forced_full": full,
            "backend": _backend(),
        }
        if scoped is not None:
            detail["speedup_vs_full"] = round(
                full["prefix_churn_p50_ms"]
                / max(scoped["prefix_churn_p50_ms"], 1e-6),
                1,
            )
        print(
            json.dumps(
                {
                    "metric": "prefix_churn_p50_ms",
                    "value": head["prefix_churn_p50_ms"],
                    "unit": "ms",
                    "vs_baseline": None,
                    "detail": detail,
                }
            )
        )
        if args.smoke and scoped is not None:
            # CI gate: the scoped pipeline must take the prefix-only
            # path for every churn round (the initial build is the one
            # full) and run ZERO SPF solves
            _smoke_gate("prefix-churn", scoped, {
                "prefix-only path every round": (
                    scoped["rebuild_prefix_only"] >= args.prefix_rounds - 1
                ),
                "one initial full build": scoped["rebuild_full"] == 1,
                "zero churn solves": scoped["churn_area_solves"] == 0,
            })
        return

    from openr_tpu.utils import topogen

    # 3-tier fat-tree with ~args.nodes nodes: 5k^2/4 = n → k
    k = max(4, int(round((args.nodes * 4 / 5) ** 0.5 / 2)) * 2)
    adj_dbs, prefix_dbs = topogen.fat_tree(k, metric=10)
    dec, pubs, routes, pub_for = build_decision(
        adj_dbs, prefix_dbs,
        debounce_min=args.debounce_min_ms, debounce_max=args.debounce_max_ms,
    )

    n_flaps, spf_runs, spf_ms, lat, no_change, breakdown, trace_ms = asyncio.new_event_loop().run_until_complete(
        churn(
            dec, pubs, routes, pub_for, list(adj_dbs),
            args.flaps_per_sec, args.seconds, burst=args.burst,
        )
    )
    spf = np.array(spf_ms) if spf_ms else np.array([0.0])
    latency = np.array(lat) if lat else np.array([0.0])
    out = {
        "metric": "churn_steady_state_recompute_p50_ms",
        "value": round(float(np.percentile(spf, 50)), 3),
        "unit": "ms",
        "vs_baseline": None,
        "detail": {
            "config": 5,
            "nodes": len(adj_dbs),
            "k": k,
            "flaps_sent": n_flaps,
            "flap_rate_target": args.flaps_per_sec,
            "burst": args.burst,
            "recomputes": spf_runs,
            "flaps_per_recompute": round(n_flaps / max(spf_runs, 1), 1),
            "no_change_flaps": no_change,
            "spf_p99_ms": round(float(np.percentile(spf, 99)), 3),
            "flap_to_rib_p50_ms": round(float(np.percentile(latency, 50)), 3),
            "flap_to_rib_p99_ms": round(float(np.percentile(latency, 99)), 3),
            # PerfEvents-derived convergence (sampled 1-in-50 flaps,
            # KVSTORE_FLOODED → ROUTE_UPDATE_SENT per-stage markers) —
            # the trace-based counterpart of flap_to_rib_p50_ms
            "convergence_p50_ms": (
                round(float(np.percentile(np.array(trace_ms), 50)), 3)
                if trace_ms else None
            ),
            "convergence_traces": len(trace_ms),
            "rebuild_breakdown_p50_ms": {
                k: round(float(np.percentile(np.array(v), 50)), 2)
                for k, v in breakdown.items()
            },
            # byte-splice decode tiers (decision.py _decode_adj_fast):
            # "fast" should dominate under single-flap-per-key churn
            "decode_stats": dict(dec.decode_stats),
            "backend": _backend(),
        },
    }
    print(json.dumps(out))


def _backend() -> str:
    import jax

    return jax.default_backend()


if __name__ == "__main__":
    main()
