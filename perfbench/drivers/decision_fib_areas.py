"""Driver: circuit events through one border router's Decision + Fib, in
areas, closed loop.

`decision_fib` for a deployment that is split into areas and whose
prefixes state their own forwarding: the same wiring (one real
`Decision(solver="tpu")`, a real `Fib` + `MockFibHandler`, the queues
`node.py` builds), the same feed, the same event clock and the same series
and counters, so that the metric files the flap cells use read it
unchanged. What differs:

  * the databases come from the graph's `meta`
    (`perfbench/topologies/backbone_sites.py`): one `AdjacencyDatabase`
    and one `PrefixDatabase` a router an area it sits in, names from
    `meta["names"]`, a router's loopback and node label the same in each
    of its areas;
  * every loopback is advertised with the configuration's
    `prefixes.forwarding_algorithm` and `.forwarding_type`;
  * `config["decision"]` also gives `ksp_paths` and `enable_lfa`;
  * an event's `Publication` carries the area of the circuit it changes;
  * a restore puts the circuit back to its own configured metric
    (`meta["own_metric"]`), not to the mix's `restored_metric`, which is
    there for `perfbench/control.py` alone (it knows one number).

Traffic parameters, series and the two ways an event ends: as
`perfbench/drivers/decision_fib.py`'s docstring says.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

from perfbench import compare, topo, work_ksp
from perfbench.drivers.decision_fib import NO_CHANGE_POLL_S
from perfbench.events import flap_sequence, link_pool, root_of, warm_up_rounds


def program_dbs(g: topo.Graph, prefixes: dict):
    """The graph as the program's databases: ({(area, node): adjacency
    database}, {(area, node): prefix database})."""
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import (
        Adjacency,
        AdjacencyDatabase,
        ForwardingAlgorithm,
        ForwardingType,
        PrefixDatabase,
        PrefixEntry,
    )

    names, areas = g.meta["names"], g.meta["areas"]
    algorithm = ForwardingAlgorithm[prefixes["forwarding_algorithm"]]
    fwd_type = ForwardingType[prefixes["forwarding_type"]]
    adjs: dict[tuple[int, int], list] = {}
    for u, v, m, a in zip(
        g.src.tolist(), g.dst.tolist(), g.metric.tolist(),
        g.meta["edge_area"].tolist(),
    ):
        adjs.setdefault((a, u), []).append(Adjacency(
            other_node_name=names[v],
            if_name=topo.if_name(u, v),
            other_if_name=topo.if_name(v, u),
            metric=m,
        ))
    adj_dbs, prefix_dbs = {}, {}
    for i, in_areas in enumerate(g.meta["node_areas"]):
        for a in in_areas:
            adj_dbs[(a, i)] = AdjacencyDatabase(
                this_node_name=names[i],
                adjacencies=tuple(adjs.get((a, i), ())),
                node_label=topo.node_label(i),
                area=areas[a],
            )
            prefix_dbs[(a, i)] = PrefixDatabase(
                this_node_name=names[i],
                prefix_entries=(PrefixEntry(
                    prefix=IpPrefix.make(topo.loopback(i)),
                    forwarding_type=fwd_type,
                    forwarding_algorithm=algorithm,
                ),),
                area=areas[a],
            )
    return adj_dbs, prefix_dbs


def circuit_events(g: topo.Graph, pool, rng, traffic):
    """`events.flap_sequence`, with every restore going back to the
    circuit's own configured metric."""
    own = g.meta["own_metric"]
    raised = int(traffic["raised_metric"])
    for (a, b), metric in flap_sequence(pool, rng, traffic):
        yield (a, b), metric if metric == raised else int(own[g.edge_slot(a, b)])


def run(ctx) -> dict:
    return asyncio.run(_run(ctx))


async def _run(ctx) -> dict:
    from openr_tpu.common import constants as C
    from openr_tpu.config import AreaConfig, Config
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
    no_change_completes = bool(traffic.get("no_change_completes", False))
    rng = np.random.default_rng(ctx["seed"])

    t = time.perf_counter()
    g = topo.build(config["topology"])
    root = root_of(g, config["root"])
    names, areas = g.meta["names"], g.meta["areas"]
    me = names[root]
    adj_dbs, prefix_dbs = program_dbs(g, config["prefixes"])
    say(
        f"{g.n} routers, {g.num_edges} directed adjacencies in "
        f"{len(areas)} areas, node under test {me} (generated in "
        f"{time.perf_counter() - t:.1f}s)"
    )

    # ---- one router's Decision + Fib, wired as node.py wires them ----
    cfg = Config.default(me)
    cfg.node.areas = tuple(AreaConfig(area_id=a) for a in areas)
    cfg.node.decision.native_rib = config["decision"]["native_rib"]
    cfg.node.decision.ksp_paths = int(config["decision"]["ksp_paths"])
    cfg.node.decision.enable_lfa = bool(config["decision"]["enable_lfa"])
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
    versions = dict.fromkeys(adj_dbs, 1)

    def adj_value(key):
        db = adj_dbs[key]
        return Value(
            version=versions[key],
            originator_id=db.this_node_name,
            value=to_wire(db),
        ).with_hash()

    def with_metric(a: int, b: int, metric: int) -> tuple[int, list]:
        """The a<->b circuit's area, and the keys of both ends' databases
        in it, each with that adjacency at `metric`."""
        area = int(g.meta["edge_area"][g.edge_slot(a, b)])
        keys = []
        for u, v in ((a, b), (b, a)):
            db = adj_dbs[(area, u)]
            adj_dbs[(area, u)] = dataclasses.replace(db, adjacencies=tuple(
                dataclasses.replace(x, metric=metric)
                if x.other_node_name == names[v] else x
                for x in db.adjacencies
            ))
            keys.append((area, u))
        return area, keys

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

    flaps = circuit_events(
        g, link_pool(g, traffic["links"], root), rng, traffic
    )
    stats = counters.stats  # the Fib's own fib.program_ms timer, read per event

    async def programmed_or_no_change(t_push: float):
        """The event's trace back from Fib, or None once Decision has
        published, with no route change, a rebuild begun after `t_push`."""
        deadline = time.monotonic() + timeout
        while True:
            trace = traces.try_get()
            if trace is not None:
                return trace
            done_t0 = dec._last_completed_snapshot_t0
            if done_t0 > t_push and dec._last_emitted_snapshot_t0 < done_t0:
                return None
            if time.monotonic() > deadline:
                raise asyncio.TimeoutError
            await asyncio.sleep(NO_CHANGE_POLL_S)

    async def event(keep: bool) -> bool:
        """One circuit event, push -> FIB_PROGRAMMED (or, with
        `no_change_completes`, -> the rebuild that proved no route
        changed); True when it completed."""
        (a, b), metric = next(flaps)
        area, changed = with_metric(a, b, metric)
        g.set_metric(a, b, metric)
        for key in changed:
            versions[key] += 1
        pub = Publication(
            area=areas[area],
            key_vals={C.adj_key(adj_dbs[key].this_node_name): adj_value(key)
                      for key in changed},
            perf_events=perf.PerfEvents.start(
                perf.KVSTORE_FLOODED, node="perfbench"
            ),
        )
        stat = stats.get("fib.program_ms")
        fib_ms0 = stat.sum if stat is not None else 0.0
        t0 = time.perf_counter()
        kvstore_pubs.push(pub)
        try:
            if no_change_completes:
                trace = await programmed_or_no_change(t0)
            else:
                trace = await asyncio.wait_for(traces.get(), timeout)
        except asyncio.TimeoutError:
            return False
        t1 = time.perf_counter()
        if trace is None:
            markers = pub.perf_events.deltas()[1:] + [
                (perf.ROUTE_UPDATE_SENT, 0.0), (perf.FIB_PROGRAMMED, 0.0),
            ]
        elif trace.last_event() != perf.FIB_PROGRAMMED:
            return False
        else:
            markers = trace.deltas()[1:]
        if keep:
            record("latency_ms", (t1 - t0) * 1e3)
            if no_change_completes:
                record("no_change", float(trace is None))
            for name, ms in markers:
                record(f"marker.{name}_ms", ms)
            for name, ms in dec.last_breakdown_ms.items():
                record(f"decision.{name}_ms", ms)
            record("fib.program_ms", stats["fib.program_ms"].sum - fib_ms0)
        return True

    await dec.start()
    await fib.start()
    try:
        # ---- the LSDB: one publication per router and area, as the flood
        # would bring them; the first RIB waits for KVSTORE_SYNCED ----
        t = time.perf_counter()
        for i, key in enumerate(adj_dbs):
            area = areas[key[0]]
            name = adj_dbs[key].this_node_name
            kv = {C.adj_key(name): adj_value(key)}
            for entry in prefix_dbs[key].prefix_entries:
                kv[C.prefix_key(name, area, str(entry.prefix))] = Value(
                    version=1, originator_id=name,
                    value=to_wire(prefix_dbs[key]),
                ).with_hash()
            kvstore_pubs.push(Publication(area=area, key_vals=kv))
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
    if no_change_completes:
        delta["events_no_change"] = sum(series.get("no_change", ()))
    if since["compiles"] or since["backend_compiles"]:
        say(f"compiled inside the window: {since['compiled_fns']}")
    failed += int(delta.get("decision.rebuild.failed", 0))
    failed += int(delta.get("decision.spf.engine_native", 0))
    say(
        "window counters: "
        + ", ".join(
            f"{k}={delta.get(k, 0):.0f}" for k in (
                "decision.rebuild.topo_delta", "decision.rebuild.full",
                "decision.rebuild.cached_areas", "decision.spf.warm_starts",
                "decision.spf.engine_device", "decision.spf.engine_native",
                "decision.spf.ksp_jobs", "decision.spf.ksp_chunks",
                "decision.dev_cache.patches", "decision.dev_cache.uploads",
                "meter.fetched_bytes",
            )
        )
    )
    return {
        "attempted": attempted,
        "failed": failed,
        "series": series,
        "counters": delta,
        "checks": checks,
        "work": {"ksp_areas": work_ksp.batch_counts(
            g, compare.plain_unicast(checks[-1]["unicast"]))},
    }

