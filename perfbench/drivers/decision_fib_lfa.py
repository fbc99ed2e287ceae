"""Driver: `decision_fib_areas` with the backups of every unicast route in
the comparison.

`perfbench/drivers/decision_fib_areas.py`, run as it is: the same wiring
(one real `Decision(solver="tpu")`, a real `Fib` + `MockFibHandler`, the
queues `node.py` builds), the same feed, circuit events, event clock,
series and counters, so that every metric file the backbone cell uses
reads this driver unchanged. The configuration gives
`decision.enable_lfa`, which that driver already reads.

What it adds: Fib programs no backup next hop (`RibEntry.backup_nexthops`
is surfaced for fast-reroute consumers), so the handler's tables hold
none. Each time that driver takes a table for the comparison (it asks the
handler for its unicast routes), this one takes, at the same instant, the
backups of every unicast route as Decision published them
(`Decision.get_route_db()`, the merge book Fib was handed), and puts them
into the kept table beside the routes, one row a route that has any,
under the key the configuration's reference gives them
(`references.backbone_lfa.backup_key`). `run.check_tables` then compares
them with the routes, unchanged. Where Decision and the handler are made
(`decision_fib_areas` looks both up as it sets up) it puts a maker that
keeps hold of each in those names for the length of the call, and puts
the old ones back. One run a process, as `run.py` makes them.

It also returns, in `work`, the least work of an event's cold solve
(`perfbench/work.py`) for the roofline: the nodes and directed edges of
one area (the two areas are the same size), the padded batch of the
root and its neighbours there, and the bytes fetched an event (the packed
buffer with its LFA bits, and the distance matrix the backups read).

Traffic parameters, series and the two ways an event ends: as
`perfbench/drivers/decision_fib.py`'s docstring says.
"""

from __future__ import annotations

import dataclasses

from perfbench import topo
from perfbench.drivers import decision_fib_areas
from perfbench.events import root_of
from perfbench.references.backbone_lfa import backup_key


@dataclasses.dataclass(frozen=True)
class BackupRow:
    """A route's backups as `compare.plain_unicast` reads a route: `dest`
    with a `prefix`, and `nexthops`."""

    dest: object
    nexthops: tuple


@dataclasses.dataclass(frozen=True)
class _Key:
    prefix: str


def backup_rows(rib) -> list[BackupRow]:
    """One row a unicast route of `rib` (a RouteDatabase) that has backups."""
    return [
        BackupRow(_Key(backup_key(str(p.prefix))), tuple(e.backup_nexthops))
        for p, e in rib.unicast_routes.items() if e.backup_nexthops
    ]


def run(ctx) -> dict:
    import openr_tpu.decision.decision as decision_module
    import openr_tpu.fib as fib_module

    made: dict = {}
    taken: list[tuple[list, list]] = []
    real_decision = decision_module.Decision
    real_handler = fib_module.MockFibHandler

    def decision(*args, **kwargs):
        made["decision"] = real_decision(*args, **kwargs)
        return made["decision"]

    def handler(*args, **kwargs):
        h = real_handler(*args, **kwargs)
        routes_of = h.get_route_table_by_client

        async def routes_and_backups(client_id):
            routes = await routes_of(client_id)
            taken.append(
                (routes, backup_rows(made["decision"].get_route_db())))
            return routes

        h.get_route_table_by_client = routes_and_backups
        return h

    decision_module.Decision = decision
    fib_module.MockFibHandler = handler
    try:
        obs = decision_fib_areas.run(ctx)
    finally:
        decision_module.Decision = real_decision
        fib_module.MockFibHandler = real_handler
    backups = {id(routes): rows for routes, rows in taken}
    n_rows = n_hops = 0
    for chk in obs["checks"]:
        rows = backups[id(chk["unicast"])]
        chk["unicast"] = [*chk["unicast"], *rows]
        n_rows += len(rows)
        n_hops += sum(len(r.nexthops) for r in rows)
    ctx["say"](
        f"kept {len(obs['checks'])} tables with {n_rows} backup rows, "
        f"{n_hops} backup next hops"
    )
    obs["work"].update(solve_work(
        ctx["config"], obs["counters"]["meter.fetched_bytes"],
        ctx["window"].events,
    ))
    return obs


def solve_work(config: dict, fetched_bytes: float, events: int) -> dict:
    """The least work of one event's cold solve (`perfbench/work.py`):
    one area of the configuration's graph (the first the root sits in;
    the areas are the same size), from the root and its neighbours
    there, with the bytes fetched an event."""
    from openr_tpu.ops.spf import pad_batch

    g = topo.build(config["topology"])
    root = root_of(g, config["root"])
    area = g.meta["edge_area"]
    mine = g.meta["node_areas"][root][0]
    n_nbrs = len(set(g.dst[(g.src == root) & (area == mine)].tolist()))
    return {
        "nodes": sum(mine in a for a in g.meta["node_areas"]),
        "edges": int((area == mine).sum()),
        "batch": int(pad_batch(1 + n_nbrs)),
        "out_bytes": fetched_bytes / max(events, 1),
    }
