"""Driver: drain and undrain events through one router's Decision + Fib,
closed loop.

`decision_fib` for events that are not a link's metric: one switch of the
fabric sets its node-overload bit, or clears it (Open/R `LinkMonitor`
`setNodeOverload` / `unsetNodeOverload`). The same wiring (one real
`Decision(solver="tpu")`, a real `Fib` + `MockFibHandler`, the queues
`node.py` builds), the same feed, the same event clock and the same series
and counters, so that the metric files the flap cells use read it
unchanged. What differs:

  * the graph is a `perfbench/topologies/fat_tree_drained.py` graph: the
    switches of its standing set (`meta["drained"]`) are fed with
    `is_overloaded` true and stay so;
  * an event is one switch's own `AdjacencyDatabase`, `is_overloaded`
    flipped and nothing else changed, version + 1: one key in one
    `Publication`. To Decision that is structural dirt: the rebuild is
    the full one (CSR, split tables, upload, cold kernel, election and
    assembly of every route, the whole-RIB diff);
  * the switches come from `meta["drain_pool"]` by `drain_sequence`;
  * the tables kept for the comparison carry a graph whose `meta` holds
    its own copy of the drained set as it stood
    (`fat_tree_drained.as_published`).

Traffic parameters (perfbench/traffic/<mix>.json):
  max_drained      switches the traffic holds drained at any moment
                   (drain ... undrain the oldest)
  warmup_events / warmup_quiet / check_samples / event_timeout_s
                   as `perfbench/drivers/decision_fib.py`'s docstring says
  links / metric_range   not read here: they are `perfbench/control.py`'s,
                   which knows links and metrics alone; with them it
                   compares the reference and its control on the graph
                   with the standing set drained

Every event of the pool changes routes (a plane toward one pod dies or
returns), so an event ends at its trace back from Fib at FIB_PROGRAMMED
and at nothing else. Series: `latency_ms`, `marker.<NAME>_ms`,
`decision.<span>_ms`, `fib.program_ms`, one entry an event each; `work`:
the least work of an event's cold solve, as `solver_rebuild` gives it.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

from perfbench import topo
from perfbench.drivers.decision_fib import AREA, program_dbs
from perfbench.events import root_of, warm_up_rounds
from perfbench.topologies import fat_tree_drained
from perfbench.topologies.fat_tree_drained import pod_of_agg


def drain_sequence(g: topo.Graph, rng, traffic):
    """(switch, drained?) for ever: drain, drain, undrain the oldest,
    drain, undrain the oldest, ...: never more than `max_drained` switches
    held by the traffic at once, every switch its own draw from the pool,
    and never one of a pod that already holds a drained aggregation
    switch, the standing set's or the traffic's: at most one a pod, so
    that a pod keeps its other planes and every event moves the routes of
    one pod's ToRs through one plane."""
    pool = g.meta["drain_pool"]
    standing = {
        pod_of_agg(g, n) for n in g.meta["drained"] if n >= g.meta["n_core"]
    }
    held: list[int] = []
    while True:
        if len(held) < traffic["max_drained"]:
            node = int(pool[int(rng.integers(len(pool)))])
            busy = standing | {pod_of_agg(g, n) for n in held}
            if pod_of_agg(g, node) in busy:
                continue
            held.append(node)
            yield node, True
        else:
            yield held.pop(0), False


def run(ctx) -> dict:
    return asyncio.run(_run(ctx))


async def _run(ctx) -> dict:
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
    from openr_tpu.ops.spf import pad_batch
    from openr_tpu.types.kvstore import Publication, Value
    from openr_tpu.types.serde import to_wire

    config, traffic = ctx["config"], ctx["traffic"]
    window, meter, say = ctx["window"], ctx["meter"], ctx["say"]
    timeout = float(traffic.get("event_timeout_s", 120))
    rng = np.random.default_rng(ctx["seed"])

    t = time.perf_counter()
    g = topo.build(config["topology"])
    root = root_of(g, config["root"])
    me = topo.node_name(root)
    adj_dbs, prefix_dbs = program_dbs(g)
    # the switches drained as published: the standing set, then the
    # traffic's on top; each is fed, and re-published, with the bit set
    drained = set(g.meta["drained"])
    for node in drained:
        adj_dbs[node] = dataclasses.replace(adj_dbs[node], is_overloaded=True)
    say(
        f"{g.n} switches, {g.num_edges} directed adjacencies, "
        f"{len(drained)} of the switches drained, node under test {me} "
        f"(generated in {time.perf_counter() - t:.1f}s)"
    )

    # ---- one router's Decision + Fib, wired as node.py wires them ----
    cfg = Config.default(me)
    cfg.node.decision.native_rib = config["decision"]["native_rib"]
    counters = Counters()
    mcfg = cfg.node.messaging
    bound = mcfg.queue_maxsize if mcfg.enforce_bounds else 0

    def queue(short, policy=None, coalesce_fn=None):
        return ReplicateQueue(
            name=f"{me}.{short}",
            maxsize=bound if policy is not None else 0,
            policy=policy, coalesce_fn=coalesce_fn,
            counters=counters, counter_key=short,
        )

    kvstore_pubs = queue("kvstore_pubs", COALESCE, coalesce_publications)
    route_updates = queue("route_updates", COALESCE, coalesce_route_updates)
    fib_updates = queue("fib_updates", COALESCE, coalesce_route_updates)
    perf_events = queue("perf_events", SHED_OLDEST)
    kvstore_synced = asyncio.Event()
    pub_reader = kvstore_pubs.get_reader()
    dec = Decision(
        cfg, pub_reader, route_updates, solver=config["decision"]["solver"],
        counters=counters, initial_sync_event=kvstore_synced,
    )
    handler = MockFibHandler()
    fib = Fib(
        cfg, route_updates.get_reader(), handler,
        fib_updates_queue=fib_updates, perf_events_queue=perf_events,
        counters=counters,
    )
    traces = perf_events.get_reader("perfbench")
    versions = {db.this_node_name: 1 for db in adj_dbs}

    def adj_value(db):
        return Value(
            version=versions[db.this_node_name],
            originator_id=db.this_node_name,
            value=to_wire(db),
        ).with_hash()

    async def settle(pred, what: str) -> None:
        deadline = time.monotonic() + timeout
        while not pred():
            if dec.last_rebuild_error is not None:
                raise RuntimeError(f"route rebuild failed: {dec.last_rebuild_error}")
            if time.monotonic() > deadline:
                raise RuntimeError(f"timed out after {timeout:.0f}s waiting for {what}")
            await asyncio.sleep(0.05)

    async def table(label: str) -> dict:
        """What the timed path has programmed, as the handler holds it,
        with the benchmark's own graph as it stands now."""
        return {
            "label": label,
            "graph": fat_tree_drained.as_published(g, drained),
            "root": root,
            "unicast": await handler.get_route_table_by_client(CLIENT_ID_OPENR),
            "mpls": await handler.get_mpls_route_table_by_client(CLIENT_ID_OPENR),
        }

    series: dict[str, list[float]] = {}

    def record(name: str, value: float) -> None:
        series.setdefault(name, []).append(value)

    drains = drain_sequence(g, rng, traffic)
    stats = counters.stats  # the Fib's own fib.program_ms timer, read per event

    async def event(keep: bool) -> bool:
        """One drain or undrain, push -> FIB_PROGRAMMED; True when it
        completed."""
        node, bit = next(drains)
        db = adj_dbs[node] = dataclasses.replace(
            adj_dbs[node], is_overloaded=bit
        )
        (drained.add if bit else drained.remove)(node)
        versions[db.this_node_name] += 1
        pub = Publication(
            area=AREA,
            key_vals={C.adj_key(db.this_node_name): adj_value(db)},
            perf_events=perf.PerfEvents.start(
                perf.KVSTORE_FLOODED, node="perfbench"
            ),
        )
        stat = stats.get("fib.program_ms")
        fib_ms0 = stat.sum if stat is not None else 0.0
        t0 = time.perf_counter()
        kvstore_pubs.push(pub)
        try:
            trace = await asyncio.wait_for(traces.get(), timeout)
        except asyncio.TimeoutError:
            return False
        t1 = time.perf_counter()
        if trace.last_event() != perf.FIB_PROGRAMMED:
            return False
        if keep:
            record("latency_ms", (t1 - t0) * 1e3)
            for name, ms in trace.deltas()[1:]:
                record(f"marker.{name}_ms", ms)
            for name, ms in dec.last_breakdown_ms.items():
                record(f"decision.{name}_ms", ms)
            record("fib.program_ms", stats["fib.program_ms"].sum - fib_ms0)
        return True

    await dec.start()
    await fib.start()
    try:
        # ---- the LSDB: one publication per switch, as the flood would
        # bring them; the first RIB waits for KVSTORE_SYNCED ----
        t = time.perf_counter()
        for i, (db, pdb) in enumerate(zip(adj_dbs, prefix_dbs)):
            name = db.this_node_name
            kv = {C.adj_key(name): adj_value(db)}
            for entry in pdb.prefix_entries:
                kv[C.prefix_key(name, AREA, str(entry.prefix))] = Value(
                    version=1, originator_id=name, value=to_wire(pdb)
                ).with_hash()
            kvstore_pubs.push(Publication(area=AREA, key_vals=kv))
            if i % 256 == 255:
                await asyncio.sleep(0)
        await settle(lambda: pub_reader.size() == 0, "pub drain")
        feed_s = time.perf_counter() - t
        t = time.perf_counter()
        kvstore_synced.set()
        await settle(
            lambda: dec.rib_computed.is_set() and fib.synced.is_set(),
            "first RIB and FIB sync",
        )
        say(
            f"fed in {feed_s:.1f}s, first RIB in {time.perf_counter() - t:.1f}s "
            f"{ {k: round(v) for k, v in dec.last_breakdown_ms.items()} }"
        )
        t = time.perf_counter()
        n_warm = 0
        for n_warm in warm_up_rounds(meter, traffic):
            if not await event(keep=False):
                raise RuntimeError("a warm-up event did not reach FIB_PROGRAMMED")
        say(f"{n_warm} warm-up events in {time.perf_counter() - t:.1f}s")

        # ---- the window ----
        sample_at = sorted(
            rng.random(int(traffic["check_samples"])) * window.seconds
        )
        checks: list[dict] = []
        before = counters.snapshot()
        mark = meter.mark()
        attempted = failed = 0
        window.open()
        while window.more():
            attempted += 1
            if not await event(keep=True):
                failed += 1
                break
            window.event_done()
            if sample_at and window.elapsed() >= sample_at[0]:
                sample_at.pop(0)
                checks.append(await table(f"event {attempted}"))
        window.close()
        checks.append(await table(f"last event {attempted}"))
        after = counters.snapshot()
        since = meter.since(mark)
    finally:
        await fib.stop()
        await dec.stop()
        for q in (kvstore_pubs, route_updates, fib_updates, perf_events):
            q.close()

    delta = {
        k: after[k] - before.get(k, 0) for k in after
        if isinstance(after[k], (int, float))
    }
    delta["meter.compiles"] = since["compiles"]
    delta["meter.backend_compiles"] = since["backend_compiles"]
    delta["meter.fetched_bytes"] = since["fetched_bytes"]
    if since["compiles"] or since["backend_compiles"]:
        say(f"compiled inside the window: {since['compiled_fns']}")
    failed += int(delta.get("decision.rebuild.failed", 0))
    failed += int(delta.get("decision.spf.engine_native", 0))
    say(
        "window counters: "
        + ", ".join(
            f"{k}={delta.get(k, 0):.0f}" for k in (
                "decision.rebuild.full", "decision.rebuild.structural",
                "decision.rebuild.topo_delta", "decision.spf.engine_device",
                "decision.spf.engine_native", "decision.dev_cache.uploads",
                "decision.dev_cache.upload_bytes", "meter.fetched_bytes",
            )
        )
    )
    # the least work of an event's cold solve (perfbench/work.py), for
    # the roofline's reader: as `solver_rebuild` counts it
    n_nbrs = int((g.src == root).sum())
    work = {
        "nodes": g.n, "edges": g.num_edges,
        "batch": int(pad_batch(1 + n_nbrs)),
        "out_bytes": since["fetched_bytes"] / max(window.events, 1),
    }
    return {
        "attempted": attempted,
        "failed": failed,
        "series": series,
        "counters": delta,
        "checks": checks,
        "work": work,
    }
