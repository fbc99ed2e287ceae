#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

KvStore publication → Decision → TpuSpfSolver on the device → route delta
→ Fib → MockFibHandler, through the entry points the product uses, at a
size an Open/R operator would call real. One process, JAX touched once,
no network, everything generated from --seed. Legs:

  A  the whole module graph, small: the in-process emulator (Spark →
     LinkMonitor → KvStore → Decision → Fib per node) on the 20-switch
     fat_tree(4), solver "tpu" with the native host engine off; converge,
     fail and heal one link, FIB == oracle on every node.
  B  the served path at deployment size: a k=90 fat-tree (10,125
     switches, 729k directed adjacencies, hop-count metrics, one loopback
     per node — BASELINE.json config 2) delivered as Publications into a
     real Decision wired to a real Fib + MockFibHandler by the queues
     node.py builds; first RIB, three metric flaps (warm-start kernel +
     on-device scatter patches), one link-down (full re-solve). After
     every step the dataplane table equals the scalar oracle.
  C  the repo's stated full width: erdos_renyi_lsdb(100k, deg 20) through
     TpuSpfSolver.compute_routes from node-0; the RIB equals the scalar
     oracle's and the native engine's, sampled batch columns equal the
     C++ Dijkstra.
  D  four chips, only when jax.device_count() >= 4: a route-server
     request for 8 ToRs of leg B's fabric over 4x1 and 2x2 meshes equals
     the single-device call and the oracle.

Exit code 0 and a last stdout line of exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
(the device as JAX reports it) only when every leg that ran passed on a
TPU. The line before it, "report: {...}", carries the detail: per-leg ok,
the engine that solved, compile counts and seconds, bytes fetched, routes
programmed. Any other platform is exit 1 with the platform named and no
stdout at all, unless --allow-cpu (debugging; --tiny cuts the sizes so
the command can be exercised on a CPU-only host). Every stdout line but
the last is labelled with the platform it ran on.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import faulthandler
import json
import logging
import os
import subprocess
import sys
import threading
import time
import traceback
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: the whole run must end inside the driver's 1200 s; a hang in a device
#: call cannot be interrupted from Python, so a watchdog dumps every
#: thread's stack and exits 1 shortly before that
WATCHDOG_S = 1150

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"


class Failed(Exception):
    """A leg's check did not hold."""


class Meter:
    """Compile and transfer accounting for one leg or step: XLA backend
    compile requests and the seconds they took (a persistent-cache hit
    counts its retrieval time), cache hits/writes, jitted-function
    compiles by name (the repo's compile ledger) and device→host bytes
    at the solver's transfer seams."""

    def __init__(self):
        import jax

        from openr_tpu.monitor import compile_ledger

        self._lock = threading.Lock()
        self._compile_s = 0.0
        self._backend_compiles = 0
        self._cache_hits = 0
        self._cache_writes = 0
        self._ledger = compile_ledger.install()
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            with self._lock:
                self._compile_s += secs
                self._backend_compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == _CACHE_HIT_EVENT:
                self._cache_hits += 1
            elif event == _CACHE_WRITE_EVENT:
                self._cache_writes += 1

    def mark(self) -> dict:
        with self._lock:
            return {
                "compile_s": self._compile_s,
                "backend_compiles": self._backend_compiles,
                "cache_hits": self._cache_hits,
                "cache_writes": self._cache_writes,
                "fns": self._ledger.snapshot(),
                "fetched_bytes": self._ledger.host_bytes,
            }

    def since(self, mark: dict) -> dict:
        now = self.mark()
        fns = mark["fns"].delta(now["fns"])
        return {
            "compiles": sum(fns.values()),
            "compiled_fns": fns,
            "backend_compiles": (
                now["backend_compiles"] - mark["backend_compiles"]
            ),
            "compile_s": round(now["compile_s"] - mark["compile_s"], 3),
            "cache_hits": now["cache_hits"] - mark["cache_hits"],
            "cache_writes": now["cache_writes"] - mark["cache_writes"],
            "fetched_bytes": now["fetched_bytes"] - mark["fetched_bytes"],
        }


async def settle(pred, timeout: float, what: str, check=None) -> None:
    """Poll `pred` until true; `check` runs every round and raises to
    fail fast (a Decision whose rebuild raised never gets there)."""
    deadline = time.monotonic() + timeout
    while True:
        if check is not None:
            check()
        if pred():
            return
        if time.monotonic() > deadline:
            raise Failed(f"timed out after {timeout:.0f}s waiting for {what}")
        await asyncio.sleep(0.05)


def raise_rebuild_errors(decisions) -> None:
    for dec in decisions:
        if dec.last_rebuild_error is not None:
            raise Failed(
                f"{dec.name}: route rebuild failed: {dec.last_rebuild_error}"
            )


def engine_of(device: float, native: float) -> str:
    if native:
        return "native" if not device else "mixed"
    return "device" if device else "none"


def require_device_engine(leg: str, device: float, native: float) -> str:
    engine = engine_of(device, native)
    if engine != "device":
        raise Failed(
            f"leg {leg}: solves ran on engine {engine!r} "
            f"(device={device:.0f}, native={native:.0f}), expected 'device'"
        )
    return engine


# --------------------------------------------------------------------- leg A


async def leg_a(say, meter: Meter, timeout: float) -> dict:
    from openr_tpu.emulator.cluster import Cluster
    from openr_tpu.emulator.invariants import check_fib_oracle_parity
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.utils import topogen

    mark = meter.mark()
    adj_dbs, _ = topogen.fat_tree(4)
    edges = topogen.edges_of(adj_dbs)

    def device_only(ncfg):
        return dataclasses.replace(
            ncfg,
            decision=dataclasses.replace(ncfg.decision, native_rib="off"),
        )

    cluster = Cluster.from_edges(
        edges, solver="tpu", node_config_transform=device_only
    )
    decisions = [n.decision for n in cluster.nodes.values()]

    def check():
        raise_rebuild_errors(decisions)

    def clean():
        return cluster.converged() and not check_fib_oracle_parity(cluster)

    # the last tor and its first agg: one of the tor's two uplinks
    tor, agg = f"node-{len(adj_dbs) - 1}", None
    for a, b in edges:
        if tor in (a, b):
            agg = b if a == tor else a
            break
    agg_lb = IpPrefix.make(
        cluster.nodes[agg].config.node.originated_prefixes[0].prefix
    )

    def via():
        r = cluster.nodes[tor].fib.programmed_unicast.get(agg_lb)
        return {nh.neighbor_node for nh in r.nexthops} if r else set()

    t0 = time.perf_counter()
    await cluster.start()
    try:
        await settle(clean, timeout, "emulator convergence", check)
        conv_s = time.perf_counter() - t0
        say(f"A: {len(cluster.nodes)} nodes converged in {conv_s:.1f}s")
        await settle(
            lambda: via() == {agg}, timeout, f"{tor} direct to {agg}", check
        )
        cluster.fail_link(tor, agg)
        await settle(
            lambda: via() and agg not in via() and clean(),
            timeout, f"{tor} to route around failed link to {agg}", check,
        )
        say(f"A: link {tor}-{agg} failed, rerouted via {sorted(via())}")
        cluster.heal_link(tor, agg)
        await settle(
            lambda: via() == {agg} and clean(),
            timeout, f"{tor} to return to healed link to {agg}", check,
        )
        violations = check_fib_oracle_parity(cluster)
        if violations:
            raise Failed(f"leg A: FIB != oracle: {violations[:3]}")
        dev = sum(
            n.counters.get("decision.spf.engine_device")
            for n in cluster.nodes.values()
        )
        nat = sum(
            n.counters.get("decision.spf.engine_native")
            for n in cluster.nodes.values()
        )
        routes = sum(
            len(n.fib.programmed_unicast) + len(n.fib.programmed_mpls)
            for n in cluster.nodes.values()
        )
    finally:
        await cluster.stop()
    return {
        "ok": True,
        "engine": require_device_engine("A", dev, nat),
        "nodes": len(decisions),
        "device_solves": int(dev),
        "routes_programmed": routes,
        "converge_s": round(conv_s, 2),
        **meter.since(mark),
    }


# --------------------------------------------------------------------- leg B


def fabric(k: int):
    """The k-ary fat-tree and the ids the flaps are drawn from."""
    from openr_tpu.utils import topogen

    adj_dbs, prefix_dbs = topogen.fat_tree(k)
    half = k // 2
    n_core, n_agg = half * half, k * half

    def agg(pod, i):
        return n_core + pod * half + i

    def tor(pod, i):
        return n_core + n_agg + pod * half + i

    return adj_dbs, prefix_dbs, agg, tor


def lsdb_of(adj_dbs, prefix_dbs):
    from openr_tpu.decision.linkstate import LinkState, PrefixState

    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    return ls, ps


def dataplane_form(rdb) -> tuple[dict, dict]:
    """A RouteDatabase as the (unicast, mpls) tables a FibService holds."""
    return (
        {p: e.to_unicast_route() for p, e in rdb.unicast_routes.items()},
        {lbl: e.to_mpls_route() for lbl, e in rdb.mpls_routes.items()},
    )


def oracle_tables(ls, ps, node: str) -> tuple[dict, dict]:
    """The scalar reference RIB, in dataplane form."""
    from openr_tpu.decision.oracle import compute_routes

    return dataplane_form(compute_routes(ls, ps, node, vectorize=False))


def diff_tables(what: str, got: dict, want: dict) -> None:
    if got != want:
        bad = [k for k in set(got) | set(want) if got.get(k) != want.get(k)]
        raise Failed(
            f"{what}: {len(bad)} of {len(want)} routes differ from the "
            f"oracle, e.g. {sorted(map(str, bad))[:3]}"
        )


async def leg_b(say, meter: Meter, k: int, seed: int, timeout: float) -> dict:
    import numpy as np

    from openr_tpu.common import constants as C
    from openr_tpu.config import Config
    from openr_tpu.decision.decision import Decision
    from openr_tpu.fib import Fib, MockFibHandler
    from openr_tpu.fib.fib import CLIENT_ID_OPENR
    from openr_tpu.messaging import COALESCE, SHED_OLDEST, ReplicateQueue
    from openr_tpu.messaging.policies import (
        coalesce_publications,
        coalesce_route_updates,
    )
    from openr_tpu.monitor import Counters, perf
    from openr_tpu.types.kvstore import Publication, Value
    from openr_tpu.types.serde import to_wire

    mark = meter.mark()
    t0 = time.perf_counter()
    adj_dbs, prefix_dbs, agg, tor = fabric(k)
    me = adj_dbs[tor(0, 0)].this_node_name
    area = C.DEFAULT_AREA
    n_adj = sum(len(db.adjacencies) for db in adj_dbs)
    say(
        f"B: k={k} fat-tree, {len(adj_dbs)} switches, {n_adj} directed "
        f"adjacencies, node under test {me} "
        f"(generated in {time.perf_counter() - t0:.1f}s)"
    )

    # ---- one router's Decision + Fib, wired as node.py wires them ----
    cfg = Config.default(me)
    cfg.node.decision.native_rib = "off"
    counters = Counters()
    mcfg = cfg.node.messaging
    bound = mcfg.queue_maxsize if mcfg.enforce_bounds else 0

    def queue(short, policy=None, coalesce_fn=None):
        return ReplicateQueue(
            name=f"{me}.{short}",
            maxsize=bound if policy is not None else 0,
            policy=policy,
            coalesce_fn=coalesce_fn,
            counters=counters,
            counter_key=short,
        )

    kvstore_pubs = queue("kvstore_pubs", COALESCE, coalesce_publications)
    route_updates = queue("route_updates", COALESCE, coalesce_route_updates)
    fib_updates = queue("fib_updates", COALESCE, coalesce_route_updates)
    perf_events = queue("perf_events", SHED_OLDEST)
    kvstore_synced = asyncio.Event()  # KvStore.initial_sync_done's role
    pub_reader = kvstore_pubs.get_reader()
    dec = Decision(
        cfg, pub_reader, route_updates, solver="tpu", counters=counters,
        initial_sync_event=kvstore_synced,
    )
    handler = MockFibHandler()
    fib = Fib(
        cfg, route_updates.get_reader(), handler,
        fib_updates_queue=fib_updates, perf_events_queue=perf_events,
        counters=counters,
    )
    traces = perf_events.get_reader("chip_smoke")

    def check():
        raise_rebuild_errors([dec])

    versions = {db.this_node_name: 1 for db in adj_dbs}

    def adj_value(db):
        return Value(
            version=versions[db.this_node_name],
            originator_id=db.this_node_name,
            value=to_wire(db),
        ).with_hash()

    async def dataplane():
        return (
            {r.dest: r for r in
             await handler.get_route_table_by_client(CLIENT_ID_OPENR)},
            {r.top_label: r for r in
             await handler.get_mpls_route_table_by_client(CLIENT_ID_OPENR)},
        )

    async def verify(step: str) -> tuple[int, float]:
        """Dataplane table == scalar oracle on Decision's own LSDB;
        outside every timed region."""
        t = time.perf_counter()
        want_u, want_m = oracle_tables(
            dec.link_states[area].snapshot(),
            dec.prefix_states[area].snapshot(),
            me,
        )
        got_u, got_m = await dataplane()
        diff_tables(f"leg B {step}: unicast FIB", got_u, want_u)
        diff_tables(f"leg B {step}: mpls FIB", got_m, want_m)
        return len(got_u) + len(got_m), time.perf_counter() - t

    steps: list[dict] = []

    def breakdown() -> dict:
        """Decision's own split of its last rebuild (host clock)."""
        return {k: round(v, 1) for k, v in dec.last_breakdown_ms.items()}

    async def step(
        name: str, changed: list, path: str, compiles_allowed: bool
    ) -> None:
        """Publish the changed adjacency databases as ONE publication
        (a flood batch) carrying a convergence trace, wait for its
        FIB_PROGRAMMED marker, verify, and hold the rebuild to the path
        (and the compile budget) the step is there to exercise."""
        m = meter.mark()
        before = counters.snapshot()
        for db in changed:
            versions[db.this_node_name] += 1
        pub = Publication(
            area=area,
            key_vals={C.adj_key(db.this_node_name): adj_value(db)
                      for db in changed},
            perf_events=perf.PerfEvents.start(
                perf.KVSTORE_FLOODED, node="chip_smoke"
            ),
        )
        waiter = asyncio.ensure_future(traces.get())
        try:
            kvstore_pubs.push(pub)
            await settle(
                waiter.done, timeout, f"FIB_PROGRAMMED ({name})", check
            )
            trace = waiter.result()
        finally:
            waiter.cancel()
        if trace.last_event() != perf.FIB_PROGRAMMED:
            raise Failed(f"leg B {name}: trace ended at {trace.last_event()}")
        stats = meter.since(m)
        _n, verify_s = await verify(name)
        after = counters.snapshot()

        def grew(key):
            return int(after.get(key, 0) - before.get(key, 0))

        row = {
            "step": name,
            "path": next(
                (p for p in ("topo_delta", "prefix_only", "full")
                 if grew(f"decision.rebuild.{p}")), "none",
            ),
            "event_to_fib_ms": round(trace.total_ms(), 1),
            "rebuild_ms": breakdown(),
            "fib_routes_written": grew("fib.routes_programmed"),
            "fib_equals_oracle": True,
            "oracle_s": round(verify_s, 1),
            **stats,
        }
        steps.append(row)
        say(f"B: {json.dumps(row)}")
        if row["path"] != path:
            raise Failed(
                f"leg B {name}: rebuild took the {row['path']} path, "
                f"expected {path}"
            )
        if not compiles_allowed and (
            row["compiles"] or row["backend_compiles"]
        ):
            raise Failed(
                f"leg B {name}: compiled after the first flap warmed the "
                f"kernels: {row['compiled_fns']} "
                f"({row['backend_compiles']} backend compiles)"
            )

    await dec.start()
    await fib.start()
    try:
        # ---- initial LSDB: one publication per switch, as the flood
        # would bring them; the first RIB waits for KVSTORE_SYNCED ----
        m = meter.mark()
        t0 = time.perf_counter()
        for i, (db, pdb) in enumerate(zip(adj_dbs, prefix_dbs)):
            name = db.this_node_name
            kv = {C.adj_key(name): adj_value(db)}
            for entry in pdb.prefix_entries:
                kv[C.prefix_key(name, area, str(entry.prefix))] = Value(
                    version=1, originator_id=name, value=to_wire(pdb)
                ).with_hash()
            kvstore_pubs.push(Publication(area=area, key_vals=kv))
            if i % 256 == 255:
                await asyncio.sleep(0)  # let Decision's pub loop drain
        await settle(
            lambda: pub_reader.size() == 0, timeout, "pub drain", check
        )
        feed_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        kvstore_synced.set()
        await settle(
            lambda: dec.rib_computed.is_set() and fib.synced.is_set(),
            timeout, "first RIB and FIB sync", check,
        )
        first_s = time.perf_counter() - t0
        n_routes, verify_s = await verify("first RIB")
        row = {
            "step": "first_rib", "path": "full",
            "feed_s": round(feed_s, 1),
            "synced_to_fib_s": round(first_s, 1),
            "rebuild_ms": breakdown(),
            "fib_routes_written": n_routes,
            "fib_equals_oracle": True,
            "oracle_s": round(verify_s, 1),
            **meter.since(m),
        }
        steps.append(row)
        say(f"B: {json.dumps(row)}")

        # ---- flaps: the same tor-agg position in three other pods, so
        # the first one warms every kernel the later ones use ----
        rng = np.random.default_rng(seed)
        pods = [int(p) for p in rng.choice(np.arange(1, k), 3, replace=False)]

        def with_metric(a: int, b: int, metric: int | None):
            """Both ends' databases with the a<->b adjacency set to
            `metric` (None: removed)."""
            out = []
            for u, v in ((a, b), (b, a)):
                db = adj_dbs[u]
                other = adj_dbs[v].this_node_name
                adjs = tuple(
                    dataclasses.replace(x, metric=metric)
                    if x.other_node_name == other else x
                    for x in db.adjacencies
                    if metric is not None or x.other_node_name != other
                )
                adj_dbs[u] = dataclasses.replace(db, adjacencies=adjs)
                out.append(adj_dbs[u])
            return out

        links = [(agg(p, 0), tor(p, 0)) for p in pods]
        for name, link, metric, path, compiles_allowed in (
            ("flap1_raise", links[0], 10, "topo_delta", True),
            ("flap2_raise", links[1], 10, "topo_delta", False),
            ("flap3_restore", links[0], 1, "topo_delta", False),
            ("link_down", links[2], None, "full", True),
        ):
            changed = with_metric(*link, metric)
            await step(name, changed, path, compiles_allowed)
        dev = counters.get("decision.spf.engine_device")
        nat = counters.get("decision.spf.engine_native")
        table_u, table_m = await dataplane()
    finally:
        await fib.stop()
        await dec.stop()
        for q in (kvstore_pubs, route_updates, fib_updates, perf_events):
            q.close()
    return {
        "ok": True,
        "engine": require_device_engine("B", dev, nat),
        "k": k,
        "switches": len(adj_dbs),
        "directed_adjacencies": n_adj,
        "cut": None if k == 90 else f"k={k} (tiny mode)",
        "device_solves": int(dev),
        "warm_solves": int(counters.get("decision.spf.warm_starts")),
        "dev_cache_patches": int(counters.get("decision.dev_cache.patches")),
        "dev_cache_uploads": int(counters.get("decision.dev_cache.uploads")),
        "routes_in_fib": len(table_u) + len(table_m),
        "steps": steps,
        **meter.since(mark),
    }


def default_engine() -> str:
    """Which engine the DEFAULT DecisionConfig picks on this machine for
    a node's own RIB (native_rib="auto": the C++ host solver whenever
    native/build/libopenr_spf.so loads)."""
    from openr_tpu.config.config import DecisionConfig
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.utils import topogen

    adj_dbs, prefix_dbs = topogen.fat_tree(4)
    ls, ps = lsdb_of(adj_dbs, prefix_dbs)
    solver = TpuSpfSolver(native_rib=DecisionConfig().native_rib)
    solver.compute_routes(ls, ps, adj_dbs[0].this_node_name)
    s = solver.spf_kernel_stats
    return engine_of(s["engine_device"], s["engine_native"])


# --------------------------------------------------------------------- leg C


def leg_c(say, meter: Meter, n_nodes: int, seed: int) -> dict:
    import numpy as np

    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.ops.native_spf import OutCsr
    from openr_tpu.utils.topogen import erdos_renyi_lsdb

    mark = meter.mark()
    t0 = time.perf_counter()
    ls, ps, csr = erdos_renyi_lsdb(n_nodes, 20, seed, max_metric=64)
    me = "node-0"
    say(
        f"C: {csr.num_nodes} nodes, {csr.num_edges} directed edges, "
        f"{len(ps.prefixes)} prefixes "
        f"(generated in {time.perf_counter() - t0:.1f}s)"
    )
    tpu = TpuSpfSolver(native_rib="off")
    t0 = time.perf_counter()
    rdb = tpu.compute_routes(ls, ps, me)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rdb = tpu.compute_routes(ls, ps, me)
    again_s = time.perf_counter() - t0
    n_routes = len(rdb.unicast_routes) + len(rdb.mpls_routes)
    say(
        f"C: full RIB, {n_routes} routes: first call {cold_s:.1f}s "
        f"(compiles included), second call {again_s * 1e3:.0f} ms"
    )
    # every other node is reachable (the generator lays a backbone ring)
    want_routes = 2 * (csr.num_nodes - 1)
    if n_routes != want_routes:
        raise Failed(f"leg C: {n_routes} routes, expected {want_routes}")

    # ---- the RIB: device engine == the scalar oracle == the native
    # host engine (three code paths, one answer)
    t0 = time.perf_counter()
    want_u, want_m = oracle_tables(ls, ps, me)
    oracle_s = time.perf_counter() - t0
    got_u, got_m = dataplane_form(rdb)
    diff_tables("leg C: unicast RIB", got_u, want_u)
    diff_tables("leg C: mpls RIB", got_m, want_m)
    nat = TpuSpfSolver(native_rib="on").compute_routes(ls, ps, me)
    if (
        rdb.unicast_routes != nat.unicast_routes
        or rdb.mpls_routes != nat.mpls_routes
    ):
        raise Failed("leg C: device RIB differs from the native engine's")

    # ---- distances of sampled batch columns (the root's and three
    # neighbors') against the C++ Dijkstra, as bench.py checks them
    _csr, dist, _fh, nbr_ids, _lfa = tpu.solve(ls, me)
    dist = np.asarray(dist)
    my_id = csr.name_to_id[me]
    live = csr.num_nodes
    oc = OutCsr.from_arrays(
        csr.edge_src, csr.edge_dst, csr.edge_metric, csr.padded_nodes
    )
    rng = np.random.default_rng(seed)
    cols = [0, *sorted(int(c) for c in rng.choice(
        np.arange(1, 1 + len(nbr_ids)), min(3, len(nbr_ids)), replace=False
    ))]
    for col in cols:
        root = my_id if col == 0 else int(nbr_ids[col - 1])
        if not (oc.dijkstra(root)[:live] == dist[:live, col]).all():
            raise Failed(
                f"leg C: distances from root {root} (column {col}) "
                "differ from the C++ Dijkstra"
            )
    s = tpu.spf_kernel_stats
    return {
        "ok": True,
        "engine": require_device_engine(
            "C", s["engine_device"], s["engine_native"]
        ),
        "nodes": csr.num_nodes,
        "directed_edges": csr.num_edges,
        "vp": int(dist.shape[0]),
        "batch": int(dist.shape[1]),
        "routes": n_routes,
        "rib_equals_oracle": True,
        "oracle_s": round(oracle_s, 1),
        "checked_roots": len(cols),
        "first_call_s": round(cold_s, 2),
        "second_call_ms": round(again_s * 1e3, 1),
        "cut": None if n_nodes == 100_000 else f"{n_nodes} nodes (tiny mode)",
        **meter.since(mark),
    }


# --------------------------------------------------------------------- leg D


class _Warnings(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


def leg_d(say, meter: Meter, k: int, seed: int) -> dict:
    import jax
    import numpy as np

    from openr_tpu.decision.fleet import compute_fleet_ribs
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.parallel import make_mesh

    if jax.device_count() < 4:
        say(f"D: skipped: {jax.device_count()} device")
        return {"ok": None, "skipped": f"{jax.device_count()} device"}
    mark = meter.mark()
    adj_dbs, prefix_dbs, _agg, tor = fabric(k)
    ls, ps = lsdb_of(adj_dbs, prefix_dbs)
    rng = np.random.default_rng(seed)
    pods = rng.choice(np.arange(k), min(8, k), replace=False)
    tors = [adj_dbs[tor(int(p), 0)].this_node_name for p in pods]
    say(f"D: route-server request for {len(tors)} ToRs: {tors}")

    def tables(ribs):
        return {n: dataplane_form(r) for n, r in ribs.items()}

    single = tables(compute_fleet_ribs(
        ls, ps, nodes=tors, solver=TpuSpfSolver(native_rib="off")
    ))
    for n in tors:
        want_u, want_m = oracle_tables(ls, ps, n)
        diff_tables(f"leg D {n}: unicast", single[n][0], want_u)
        diff_tables(f"leg D {n}: mpls", single[n][1], want_m)
    warnings = _Warnings()
    log = logging.getLogger("openr_tpu.decision.spf_backend")
    log.addHandler(warnings)
    meshes = []
    try:
        for shape in ((4, 1), (2, 2)):
            solver = TpuSpfSolver(native_rib="off", mesh=make_mesh(*shape))
            t0 = time.perf_counter()
            got = tables(compute_fleet_ribs(ls, ps, nodes=tors, solver=solver))
            wall_s = time.perf_counter() - t0
            if got != single:
                bad = [n for n in tors if got.get(n) != single.get(n)]
                raise Failed(
                    f"leg D mesh {shape}: RIBs of {bad} differ from the "
                    "single-device call"
                )
            shard_devs = sorted({r["device"] for r in solver.last_shard_rows})
            if len(shard_devs) != 4:
                raise Failed(
                    f"leg D mesh {shape}: output shards on devices "
                    f"{shard_devs}, expected 4 distinct"
                )
            if warnings.messages:
                raise Failed(
                    f"leg D mesh {shape}: solver warned: {warnings.messages}"
                )
            # where the LSDB tables live between calls: _device_arrays
            # uploads with bare jnp.asarray, so sharded_sssp_split
            # re-lays them out over the mesh on every call
            dev = solver._device_arrays(ls.to_csr(), "split")
            table_devs = sorted(d.id for d in dev["base_nbr"].devices())
            row = {
                "mesh": f"{shape[0]}x{shape[1]}",
                "equals_single_device": True,
                "shard_devices": shard_devs,
                "tables_resident_on": table_devs,
                "first_call_s": round(wall_s, 2),
            }
            meshes.append(row)
            say(f"D: {json.dumps(row)}")
    finally:
        log.removeHandler(warnings)
    return {
        "ok": True,
        "devices": [str(d) for d in jax.devices()[:4]],
        "real_devices": jax.devices()[0].platform != "cpu",
        "tors": len(tors),
        "oracle_checked": len(tors),
        "meshes": meshes,
        **meter.since(mark),
    }


# ---------------------------------------------------------------------- main


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "not installed"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="debugging only: run on whatever platform jax finds",
    )
    ap.add_argument(
        "--tiny", action="store_true",
        help="B at k=8, C at 2,000 nodes (a CPU-sized rehearsal)",
    )
    ap.add_argument(
        "--legs", default="ABCD",
        help="subset of legs to run, e.g. BD (default: all four)",
    )
    args = ap.parse_args()
    legs = [c for c in "ABCD" if c in args.legs.upper()]
    if not (ROOT / "openr_tpu").is_dir() or not (ROOT / "native").is_dir():
        print(
            f"chip_smoke: {ROOT} holds no openr_tpu/ and native/ — the "
            "script proves the program beside it and is nothing alone",
            file=sys.stderr,
        )
        return 1
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    if args.allow_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # leg D's rehearsal needs four (virtual) host devices
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()

    import jax

    dev0 = jax.devices()[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(jax.devices()),
    }
    label = f"[platform: {dev0.platform}]"

    def say(msg: str) -> None:
        print(f"{label} {msg}", flush=True)

    if dev0.platform != "tpu" and not args.allow_cpu:
        print(
            f"chip_smoke: jax found platform {dev0.platform!r} "
            f"({dev0.device_kind}), not a TPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            "--allow-cpu --tiny is the CPU rehearsal",
            file=sys.stderr,
        )
        return 1
    say(
        f"device {device} jax {jax.__version__} jaxlib "
        f"{_version('jaxlib')} libtpu {_version('libtpu')}"
    )

    # the host engine is part of the product's default path and of leg
    # C's check: build it from the committed sources, never trust a .so
    # the copy happened to carry; a build failure is a failure
    t0 = time.perf_counter()
    subprocess.run(
        ["make", "-B", "-C", str(ROOT / "native")], check=True,
        stdout=subprocess.DEVNULL,
    )
    build_s = time.perf_counter() - t0
    say(f"native/build rebuilt from sources in {build_s:.1f}s")

    sys.path.insert(0, str(ROOT))
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    logging.getLogger("openr_tpu.decision.spf_backend").setLevel(logging.INFO)
    import openr_tpu.ops  # noqa: F401 — places the compile cache

    cache_dir = jax.config.jax_compilation_cache_dir
    say(f"compile cache: {cache_dir}")
    meter = Meter()
    k = 8 if args.tiny else 90
    n_c = 2_000 if args.tiny else 100_000
    timeout = 600.0
    runs = {
        "A": lambda: asyncio.run(leg_a(say, meter, timeout)),
        "B": lambda: asyncio.run(leg_b(say, meter, k, args.seed, timeout)),
        "C": lambda: leg_c(say, meter, n_c, args.seed),
        "D": lambda: leg_d(say, meter, k, args.seed),
    }
    t_start = time.perf_counter()
    total = meter.mark()
    results: dict[str, dict] = {}
    for leg in legs:
        t0 = time.perf_counter()
        try:
            results[leg] = runs[leg]()
        except Exception as exc:  # noqa: BLE001 — reported, and exit 1
            traceback.print_exc()
            results[leg] = {
                "ok": False, "error": f"{type(exc).__name__}: {exc}",
            }
        results[leg]["wall_s"] = round(time.perf_counter() - t0, 1)
        say(f"{leg}: {json.dumps(results[leg])}")
    default = default_engine() if "B" in legs else None
    if default is not None:
        say(f"default config (native_rib=auto) solves a RIB on: {default}")

    # a skipped leg D (ok: None) is neither a pass nor a failure
    ok = all(r["ok"] is not False for r in results.values()) and any(
        r["ok"] for r in results.values()
    )
    report = {
        "ok": ok,
        "device": device,
        "versions": {
            "jax": jax.__version__,
            "jaxlib": _version("jaxlib"),
            "libtpu": _version("libtpu"),
        },
        "mode": "tiny" if args.tiny else "full",
        "seed": args.seed,
        "compile_cache_dir": cache_dir,
        "default_engine": default,
        "legs": results,
        "total": meter.since(total),
        "wall_s": round(time.perf_counter() - t_start, 1),
    }
    faulthandler.cancel_dump_traceback_later()
    say(f"report: {json.dumps(report)}")
    # the driver's contract: the last line holds these two keys and no other
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
