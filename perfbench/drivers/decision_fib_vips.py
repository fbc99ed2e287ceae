"""Driver: `decision_fib` on a fabric whose racks announce service VIPs.

`perfbench/drivers/decision_fib.py`, run as it is: the same wiring (one
real `Decision(solver="tpu")`, a real `Fib` + `MockFibHandler`, the queues
`node.py` builds), the same feed, event clock, series and counters, so
that every metric file the flap cell uses reads this driver unchanged.
What differs is the prefix databases: a switch's `PrefixDatabase` holds
its loopback and one `PrefixEntry` a VIP it advertises, SP_ECMP / IP with
the default metrics and the `weight` the graph gives (0: none), from
`meta["vips"]` of a `perfbench/topologies/fat_tree_vips.py` graph. The
tables kept for the comparison carry the graph with its `meta`, so the
reference the configuration names sees the same VIPs.

`decision_fib.run` looks `program_dbs` up in its own module when it sets
up and takes no other builder, and a file of the benchmark is not edited
to add a cell (perfbench/README.md): this driver puts its builder in that
name for the length of the call and puts the old one back. One run a
process, as `run.py` makes them. The feed stores a switch's whole
database under each of its prefix keys (it was written for one prefix a
switch); Decision applies every copy and the result is that of one.

Traffic parameters, series and the two ways an event ends: as
`perfbench/drivers/decision_fib.py`'s docstring says.
"""

from __future__ import annotations

import dataclasses

from perfbench import topo
from perfbench.drivers import decision_fib

#: the builder this driver stands in for: one loopback a switch
loopback_dbs = decision_fib.program_dbs


def program_dbs(g: topo.Graph):
    """`decision_fib.program_dbs`, every advertiser's prefix database
    extended by its VIPs."""
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import PrefixEntry

    adj_dbs, prefix_dbs = loopback_dbs(g)
    vips = g.meta["vips"]
    indptr = vips["indptr"].tolist()
    adv, weight = vips["adv"].tolist(), vips["weight"].tolist()
    entries: dict[int, list] = {}
    for v, prefix in enumerate(vips["prefix"]):
        ip = IpPrefix.make(prefix)
        for s in range(indptr[v], indptr[v + 1]):
            entries.setdefault(adv[s], []).append(
                PrefixEntry(prefix=ip, weight=weight[s])
            )
    for node, more in entries.items():
        db = prefix_dbs[node]
        prefix_dbs[node] = dataclasses.replace(
            db, prefix_entries=(*db.prefix_entries, *more)
        )
    return adj_dbs, prefix_dbs


def run(ctx) -> dict:
    decision_fib.program_dbs = program_dbs
    try:
        return decision_fib.run(ctx)
    finally:
        decision_fib.program_dbs = loopback_dbs
