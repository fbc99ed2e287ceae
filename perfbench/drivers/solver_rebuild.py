"""Driver: full RIB rebuilds through `TpuSpfSolver.compute_routes`, closed
loop, each on a graph one link's metric away from the last.

The LSDB is built as `openr_tpu/utils/topogen.erdos_renyi_lsdb` builds it
(copied: a `CsrGraph` straight from the edge arrays behind an `LsdbView`,
adjacency details for the root only), but from the benchmark's own
`topo.Graph`, so the reference and the program read the same edges. A
metric change reaches the solver as `LinkState._apply_pending` would hand
it over: a copy of the CSR with the new metrics, a new version and the
patch journal grown by the two directed edges.

Traffic parameters (perfbench/traffic/<mix>.json):
  links          "any_not_at_root": any link that does not touch the root
  metric_range   the new metric is drawn uniformly from it
  warmup_events / warmup_quiet   calls before the window, each after a
                 metric change (the first two on chosen links, see
                 `widest_and_first`), on until so many in a row compiled nothing
  check_samples  RouteDatabases kept from seeded instants of the window,
                 besides the last, for the comparison with the reference
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench import topo
from perfbench.events import draw_link, link_pool, root_of, warm_up_rounds

AREA = "0"


def program_lsdb(g: topo.Graph, root: int):
    """(LsdbView, PrefixState, CsrGraph) of the graph."""
    from openr_tpu.common.constants import DIST_INF
    from openr_tpu.decision import linkstate
    from openr_tpu.decision.linkstate import CsrGraph, PrefixState, pad_bucket
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import PrefixDatabase, PrefixEntry
    from openr_tpu.utils.topogen import LsdbView

    e = g.num_edges
    vp = pad_bucket(g.n + 1)
    ep = pad_bucket(e, minimum=128)
    edge_src = np.zeros(ep, dtype=np.int32)
    edge_dst = np.full(ep, vp - 1, dtype=np.int32)
    edge_metric = np.full(ep, DIST_INF, dtype=np.int32)
    edge_src[:e], edge_dst[:e], edge_metric[:e] = g.src, g.dst, g.metric
    names = [topo.node_name(i) for i in range(g.n)]
    adj_details: dict = {}
    out = g.src == root
    for d, m in zip(g.dst[out].tolist(), g.metric[out].tolist()):
        adj_details.setdefault((root, d), []).append(
            (topo.if_name(root, d), m, 0, 0, topo.if_name(d, root))
        )
    ver = next(linkstate._csr_version)
    csr = CsrGraph(
        num_nodes=g.n, num_edges=e,
        edge_src=edge_src, edge_dst=edge_dst, edge_metric=edge_metric,
        node_overloaded=np.zeros(vp, dtype=bool),
        node_mask=np.arange(vp) < g.n,
        node_names=names, adj_details=adj_details,
        name_to_id={s: i for i, s in enumerate(names)},
        version=ver, base_version=ver,
    )
    ps = PrefixState()
    for i, name in enumerate(names):
        ps.update_prefix_db(PrefixDatabase(
            this_node_name=name,
            prefix_entries=(PrefixEntry(prefix=IpPrefix.make(topo.loopback(i))),),
            area=AREA,
        ))
    return LsdbView(csr), ps, csr


def patched(csr, changes: list[tuple[int, int, int]]):
    """`csr` with the directed edges (slot, dst, metric) of `changes` set:
    what `LinkState._apply_pending` makes of a metric-only change."""
    from openr_tpu.decision import linkstate

    metric = csr.edge_metric.copy()
    journal = list(csr.patches)
    for slot, dst, m in changes:
        metric[slot] = m
        journal.append(linkstate.MetricPatch(
            slot, dst, csr.dense_col(slot, dst), int(m)
        ))
    return dataclasses.replace(
        csr, edge_metric=metric, version=next(linkstate._csr_version),
        patches=tuple(journal),
    )


def run(ctx) -> dict:
    from openr_tpu.ops.spf import pad_batch
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    config, traffic = ctx["config"], ctx["traffic"]
    window, meter, say = ctx["window"], ctx["meter"], ctx["say"]
    rng = np.random.default_rng(ctx["seed"])

    t = time.perf_counter()
    g = topo.build(config["topology"])
    root = root_of(g, config["root"])
    me = topo.node_name(root)
    ls, ps, csr = program_lsdb(g, root)
    pool = link_pool(g, traffic["links"], root)
    lo, hi = traffic["metric_range"]
    say(
        f"{g.n} nodes, {g.num_edges} directed edges, root {me} "
        f"(generated in {time.perf_counter() - t:.1f}s)"
    )
    solver = TpuSpfSolver(native_rib=config["solver"]["native_rib"])
    series: dict[str, list[float]] = {}
    state = {"csr": csr, "rdb": None}

    def widest_and_first() -> list[tuple[int, int]]:
        """Two links for the warm-up: one whose edge is last among its
        destination's in-edges (it lies in the split tables' overflow part
        wherever they have one) and one whose edge is first (never there).
        A patch to each part is a program of its own."""
        first_slot = np.searchsorted(g.dst, np.arange(g.n))
        rank = np.arange(g.num_edges) - first_slot[g.dst]
        usable = (g.src != root) & (g.dst != root)
        widest = int(np.argmax(np.where(usable, rank, -1)))
        first = int(np.argmax(usable & (rank == 0)))
        return [(int(g.src[i]), int(g.dst[i])) for i in (widest, first)]

    forced = widest_and_first()

    def rebuild(keep: bool) -> bool:
        """One metric change, then one full RIB; True when it returned."""
        u, v = forced.pop(0) if forced else draw_link(pool, rng)
        m = int(rng.integers(lo, hi + 1))
        g.set_metric(u, v, m)
        state["csr"] = patched(state["csr"], [
            (g.edge_slot(u, v), v, m), (g.edge_slot(v, u), u, m),
        ])
        ls._csr = state["csr"]  # the view's one field: the CSR it shows
        t0 = time.perf_counter()
        try:
            state["rdb"] = solver.compute_routes(ls, ps, me)
        except Exception as exc:  # noqa: BLE001 — counted as a failed rebuild
            say(f"compute_routes raised {type(exc).__name__}: {exc}")
            return False
        t1 = time.perf_counter()
        if keep:
            series.setdefault("latency_ms", []).append((t1 - t0) * 1e3)
            for name, ms in solver.last_phase_ms.items():
                series.setdefault(f"solver.{name}_ms", []).append(ms)
        return True

    def table(label: str) -> dict:
        return {"label": label, "graph": g.copy(), "root": root,
                "rdb": state["rdb"]}

    t = time.perf_counter()
    state["rdb"] = solver.compute_routes(ls, ps, me)
    say(f"first call {time.perf_counter() - t:.1f}s (compiles or cache loads)")
    t = time.perf_counter()
    n_warm = 0
    for n_warm in warm_up_rounds(meter, traffic):
        if not rebuild(keep=False):
            raise RuntimeError("a warm-up rebuild raised")
    say(f"{n_warm} warm-up calls in {time.perf_counter() - t:.2f}s")

    sample_at = sorted(
        rng.random(int(traffic["check_samples"])) * window.seconds
    )
    checks: list[dict] = []
    stats0 = {**solver.spf_kernel_stats, **{
        f"dev_cache.{k}": v for k, v in solver.dev_cache_stats.items()}}
    mark = meter.mark()
    attempted = failed = 0
    window.open()
    while window.more():
        attempted += 1
        if not rebuild(keep=True):
            failed += 1
            break
        window.event_done()
        if sample_at and window.elapsed() >= sample_at[0]:
            sample_at.pop(0)
            checks.append(table(f"call {attempted}"))
    window.close()
    checks.append(table(f"last call {attempted}"))
    since = meter.since(mark)
    stats1 = {**solver.spf_kernel_stats, **{
        f"dev_cache.{k}": v for k, v in solver.dev_cache_stats.items()}}
    delta = {f"solver.{k}": stats1[k] - stats0.get(k, 0) for k in stats1}
    delta["meter.compiles"] = since["compiles"]
    delta["meter.backend_compiles"] = since["backend_compiles"]
    delta["meter.fetched_bytes"] = since["fetched_bytes"]
    if since["compiles"] or since["backend_compiles"]:
        say(f"compiled inside the window: {since['compiled_fns']}")
    failed += int(delta.get("solver.engine_native", 0))
    say("window counters: " + ", ".join(
        f"{k}={v:.0f}" for k, v in sorted(delta.items())
        if isinstance(v, (int, float))
    ))
    n_nbrs = int((g.src == root).sum())
    work = {
        "nodes": g.n, "edges": g.num_edges,
        "batch": int(pad_batch(1 + n_nbrs)),
        "out_bytes": since["fetched_bytes"] / max(window.events, 1),
    }
    # the program's device state goes before the reference runs
    del solver
    for chk in checks:
        rdb = chk.pop("rdb")
        chk["unicast"] = [e.to_unicast_route() for e in rdb.unicast_routes.values()]
        chk["mpls"] = [e.to_mpls_route() for e in rdb.mpls_routes.values()]
    return {
        "attempted": attempted,
        "failed": failed,
        "series": series,
        "counters": delta,
        "checks": checks,
        "work": work,
    }
