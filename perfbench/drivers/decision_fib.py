"""Driver: link events through one router's Decision + Fib, closed loop.

One real `Decision(solver="tpu")` wired to a real `Fib` + `MockFibHandler`
by the queues `node.py` builds, fed `Publication`s on `kvstore_pubs`: the
wiring, the publication builder and the feed of `chip_smoke.py`'s leg B
(`leg_b`, `adj_value`, `with_metric`), copied so that a change to the smoke
cannot change the benchmark. One event is in flight at a time: the next
is pushed when the last one's trace came back from Fib.

Traffic parameters (perfbench/traffic/<mix>.json):
  links            "fat_tree_tor_agg": ToR<->agg links of every pod but the
                   node's own
  raised_metric / restored_metric   what an event sets a link's metric to
  max_raised       links raised at any moment (raise ... restore oldest)
  warmup_events / warmup_quiet   events before the window: at least the
                   first, and on until the second many in a row compiled nothing
  check_samples    tables kept from seeded instants of the window, besides
                   the last, for the comparison with the reference
  event_timeout_s  an event that takes longer fails the run
"""

from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

from perfbench import topo
from perfbench.events import flap_sequence, link_pool, root_of, warm_up_rounds

AREA = "0"


def program_dbs(g: topo.Graph):
    """The graph as the program's adjacency and prefix databases
    (`openr_tpu/utils/topogen._mk_dbs`'s conventions)."""
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import (
        Adjacency,
        AdjacencyDatabase,
        PrefixDatabase,
        PrefixEntry,
    )

    adjs: list[list] = [[] for _ in range(g.n)]
    for u, v, m in zip(g.src.tolist(), g.dst.tolist(), g.metric.tolist()):
        adjs[u].append(Adjacency(
            other_node_name=topo.node_name(v),
            if_name=topo.if_name(u, v),
            other_if_name=topo.if_name(v, u),
            metric=m,
        ))
    adj_dbs = [
        AdjacencyDatabase(
            this_node_name=topo.node_name(i),
            adjacencies=tuple(adjs[i]),
            node_label=topo.node_label(i),
            area=AREA,
        )
        for i in range(g.n)
    ]
    prefix_dbs = [
        PrefixDatabase(
            this_node_name=topo.node_name(i),
            prefix_entries=(PrefixEntry(prefix=IpPrefix.make(topo.loopback(i))),),
            area=AREA,
        )
        for i in range(g.n)
    ]
    return adj_dbs, prefix_dbs


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
    say(
        f"{g.n} switches, {g.num_edges} directed adjacencies, node under "
        f"test {me} (generated in {time.perf_counter() - t:.1f}s)"
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

    def with_metric(a: int, b: int, metric: int) -> list:
        """Both ends' databases with the a<->b adjacency at `metric`."""
        out = []
        for u, v in ((a, b), (b, a)):
            db = adj_dbs[u]
            other = topo.node_name(v)
            adj_dbs[u] = dataclasses.replace(db, adjacencies=tuple(
                dataclasses.replace(x, metric=metric)
                if x.other_node_name == other else x
                for x in db.adjacencies
            ))
            out.append(adj_dbs[u])
        return out

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
            "graph": g.copy(),
            "root": root,
            "unicast": await handler.get_route_table_by_client(CLIENT_ID_OPENR),
            "mpls": await handler.get_mpls_route_table_by_client(CLIENT_ID_OPENR),
        }

    series: dict[str, list[float]] = {}

    def record(name: str, value: float) -> None:
        series.setdefault(name, []).append(value)

    flaps = flap_sequence(
        link_pool(g, traffic["links"], root), rng, traffic
    )
    stats = counters.stats  # the Fib's own fib.program_ms timer, read per event

    async def event(keep: bool) -> bool:
        """One link event, push -> FIB_PROGRAMMED; True when it completed."""
        (a, b), metric = next(flaps)
        changed = with_metric(a, b, metric)
        g.set_metric(a, b, metric)
        for db in changed:
            versions[db.this_node_name] += 1
        pub = Publication(
            area=AREA,
            key_vals={C.adj_key(db.this_node_name): adj_value(db)
                      for db in changed},
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
                "decision.rebuild.topo_delta", "decision.rebuild.full",
                "decision.spf.warm_starts", "decision.spf.engine_device",
                "decision.spf.engine_native", "decision.dev_cache.patches",
                "decision.dev_cache.uploads", "meter.fetched_bytes",
            )
        )
    )
    return {
        "attempted": attempted,
        "failed": failed,
        "series": series,
        "counters": delta,
        "checks": checks,
    }
