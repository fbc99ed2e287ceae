"""Headline benchmark: full-SPF recompute on the 100k-node/2.2M-edge LSDB.

BASELINE.json north star: "<10 ms full-SPF recompute on a 100k-node /
1M-edge LSDB ... with RIB diff == reference solver". This measures the
production recompute a node runs on a topology change, decomposed
honestly (round-2 verdict items 1-2):

  value        p50 of the batched TPU solve (distances from {self} ∪
               neighbors + ECMP first-hop matrix, host-materialized) —
               the same quantity r1/r2 reported, now on the v3
               split-width kernel (ops/spf_split.py).
  detail       the rest of the production pipeline, measured in-run:
               full_rib_ms (solve + vectorized RIB assembly over 100k
               advertised prefixes + 100k MPLS node segments),
               native_solve_ms / native_full_rib_ms (the C++ radix-heap
               single-root engine, the latency-optimal path), an
               in-run oracle equality check on sampled roots, and the
               oracle comparators MEASURED in-run (python-heapq sample
               + native C++ batch) instead of a hardcoded constant.

Timing note: every timed quantity here ends in a host materialization
(np.asarray), which is also what the production path does.

One process, one chip: this script measures in its own process and
exits non-zero, naming the platform it found, when that is not `tpu`.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

N_NODES = 100_000
AVG_DEGREE = 20  # → ~1.1M undirected edges, 2.2M directed
TARGET_MS = 10.0
METRIC_NAME = "full_spf_recompute_p50_100k_node_1m_edge"
WARMUP = 2
ITERS = 12


def _p50_p99(times: list[float]) -> tuple[float, float]:
    times = sorted(times)
    return (
        times[len(times) // 2],
        times[min(len(times) - 1, int(len(times) * 0.99))],
    )


def _report_hbm_tables(tpu, csr, detail: dict) -> None:
    """BASELINE config 3's HBM-footprint metric: resident split-kernel
    device tables for the headline topology."""
    devarrs = tpu._device_arrays(csr, "split")
    detail["hbm_tables_mb"] = round(
        sum(v.nbytes for v in devarrs.values() if hasattr(v, "nbytes"))
        / 1e6,
        1,
    )


def main() -> None:
    import jax

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        # jax carries on on the CPU when it finds no chip; a host number
        # must never be written under this benchmark's device metrics
        print(
            f"bench.py needs a TPU; jax found platform {dev0.platform!r} "
            f"({dev0.device_kind})",
            file=sys.stderr,
        )
        sys.exit(1)

    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.ops.native_spf import native_available
    from openr_tpu.utils.topogen import erdos_renyi_lsdb

    warmup, iters = WARMUP, ITERS
    ls, ps, csr = erdos_renyi_lsdb(
        N_NODES, avg_degree=AVG_DEGREE, seed=0, max_metric=64
    )

    detail: dict = {
        "nodes": csr.num_nodes,
        "directed_edges": csr.num_edges,
        "prefixes": len(ps.prefixes),
        "device": str(dev0),
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "device_count": jax.device_count(),
    }

    # ---- TPU batched engine (v3 split kernel) -------------------------
    # OPENR_BENCH_TRACE=<dir> captures an xprof trace of the timed
    # iterations (SURVEY §5.1; solve/assembly phases are annotated)
    from openr_tpu.monitor import compile_ledger, profiling

    # Per-stage compile split: every stage warms with one first call
    # that pays trace+XLA-compile; _compiled() times it and attributes
    # the ledger's compile delta to the stage, so BENCH_r0x trajectories
    # report compile_ms/compiles per stage SEPARATELY from the
    # steady-state p50s (which, post-warmup, must be pure cache hits —
    # the headline loop's compile count is asserted into the row too).
    led = compile_ledger.install()
    compile_stages: dict = {}

    def _compiled(stage: str, fn):
        before = led.snapshot()
        t0 = time.perf_counter()
        out = fn()
        ms = (time.perf_counter() - t0) * 1e3
        compile_stages[stage] = {
            "compile_ms": round(ms, 3),
            "compiles": sum(before.delta(led.snapshot()).values()),
        }
        return out

    detail["compile"] = compile_stages

    tpu = TpuSpfSolver(native_rib="off")  # batched kernel path
    solved = _compiled("headline-solve", lambda: tpu.solve(ls, "node-0"))
    for _ in range(1, warmup):
        solved = tpu.solve(ls, "node-0")
    led.mark_warm()
    times = []
    with profiling.trace(os.environ.get("OPENR_BENCH_TRACE")):
        for _ in range(iters):
            t0 = time.perf_counter()
            solved = tpu.solve(ls, "node-0")
            times.append((time.perf_counter() - t0) * 1e3)
    steady = led.compiles_since_warm()
    led.reset_warm()
    compile_stages["headline-solve"]["steady_state_compiles"] = sum(
        steady.values()
    )
    if steady:  # name the leak — this is the row a regression shows in
        compile_stages["headline-solve"]["steady_state_fns"] = sorted(
            steady
        )
    solve_p50, solve_p99 = _p50_p99(times)
    _csr, dist, _fh, nbr_ids, _ = solved
    detail["spf_batch"] = int(dist.shape[1])
    detail["tpu_solve_p99_ms"] = round(solve_p99, 3)
    detail["tpu_sources_per_sec"] = round(
        (1 + len(nbr_ids)) / (solve_p50 / 1e3), 1
    )
    # BASELINE config 3 asks for the HBM footprint: resident device
    # tables for this topology (the v3 split set the headline used).
    _report_hbm_tables(tpu, csr, detail)

    # ---- native C++ single-root engine --------------------------------
    # The native-engine and python-heapq oracle checks are host-side
    # and run right after the headline they check. Device sections
    # follow (full-rib is the production quantity, then the hop-count
    # north-star regime, then B=256 throughput).
    if native_available():
        nat = TpuSpfSolver(native_rib="on")
        nat.solve(ls, "node-0")  # build + warm the OutCsr cache
        t0 = time.perf_counter()
        nat_solved = nat.solve(ls, "node-0")
        detail["native_solve_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3
        )
        nat.compute_routes(ls, ps, "node-0")
        t0 = time.perf_counter()
        nat.compute_routes(ls, ps, "node-0")
        detail["native_full_rib_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3
        )

        # ---- in-run oracle check (north star: RIB diff == oracle) ----
        # distances: TPU batched rows vs the independent C++ Dijkstra
        from openr_tpu.ops.native_spf import OutCsr

        oc = OutCsr.from_arrays(
            csr.edge_src, csr.edge_dst, csr.edge_metric, csr.padded_nodes
        )
        my_id = csr.name_to_id["node-0"]
        roots = [my_id] + [int(x) for x in nbr_ids[:2]]
        t0 = time.perf_counter()
        ok = True
        for col, r in enumerate(roots):
            ref = oc.dijkstra(r)
            m = min(len(ref), dist.shape[0])
            if not (ref[:m] == dist[:m, col]).all():
                ok = False
                break
        detail["native_oracle_batch_ms"] = round(
            (time.perf_counter() - t0) * 1e3 / len(roots), 3
        )
        # and the native engine's fh must equal the TPU identity fh
        # (padded node dims differ: tight vs pow2 — compare live slots)
        mv = min(nat_solved[2].shape[1], _fh.shape[1], csr.num_nodes)
        ok = ok and bool(
            (nat_solved[2][: len(nbr_ids), :mv]
             == _fh[: len(nbr_ids), :mv]).all()
        )
        detail["oracle_check"] = "ok" if ok else "MISMATCH"
    else:
        detail["oracle_check"] = "native lib not built"

    # ---- python-heapq comparator, measured in-run (sampled) -----------
    import heapq

    valid = csr.edge_metric < (1 << 30)
    src = csr.edge_src[valid]
    dst = csr.edge_dst[valid]
    met = csr.edge_metric[valid]
    order = np.argsort(src, kind="stable")
    src, dst, met = src[order], dst[order], met[order]
    starts = np.searchsorted(src, np.arange(csr.padded_nodes + 1))
    t0 = time.perf_counter()
    d = np.full(csr.padded_nodes, 1 << 30, np.int64)
    d[0] = 0
    h = [(0, 0)]
    while h:
        du, u = heapq.heappop(h)
        if du != d[u]:
            continue
        for i in range(starts[u], starts[u + 1]):
            nd = du + met[i]
            v = dst[i]
            if nd < d[v]:
                d[v] = nd
                heapq.heappush(h, (int(nd), int(v)))
    py_ms = (time.perf_counter() - t0) * 1e3
    detail["python_oracle_ms_per_root"] = round(py_ms, 1)
    detail["python_oracle_est_batch_ms"] = round(
        py_ms * dist.shape[1], 1
    )
    detail["speedup_vs_python_oracle"] = round(
        py_ms * dist.shape[1] / solve_p50, 1
    )
    # the python comparison is independent of the native library, so it
    # guards the headline even on hosts where the .so was never built
    m = min(len(d), dist.shape[0])
    if not (d[:m] == dist[:m, 0]).all():
        detail["oracle_check"] = "MISMATCH(py)"
    elif detail.get("oracle_check") == "native lib not built":
        detail["oracle_check"] = "ok (python only)"

    # full production recompute: solve + RIB assembly (vectorized
    # plain-prefix path + MPLS node segments)
    _compiled(  # warm assembly caches; splits RIB-path compile cost
        "full-rib", lambda: tpu.compute_routes(ls, ps, "node-0")
    )
    times_full = []
    for _ in range(max(2, iters // 2)):
        t0 = time.perf_counter()
        rdb = tpu.compute_routes(ls, ps, "node-0")
        times_full.append((time.perf_counter() - t0) * 1e3)
    full_p50, full_p99 = _p50_p99(times_full)
    n_routes = len(rdb.unicast_routes) + len(rdb.mpls_routes)
    detail["full_rib_ms"] = round(full_p50, 3)
    detail["full_rib_p99_ms"] = round(full_p99, 3)
    # measured phase split from the solver's own timers (r05 reported
    # rib_assembly_ms: 0.0 because it was derived by SUBTRACTING the
    # headline solve p50 from the full-rib p50 — two different-loop
    # medians whose difference collapses to the clamp; the solver now
    # times its election / assembly / MPLS phases directly)
    detail["rib_election_ms"] = round(
        tpu.last_phase_ms.get("election", 0.0), 3
    )
    detail["rib_assembly_ms"] = round(
        tpu.last_phase_ms.get("assembly", 0.0), 3
    )
    detail["rib_mpls_ms"] = round(tpu.last_phase_ms.get("mpls", 0.0), 3)
    detail["routes"] = n_routes
    detail["routes_per_sec"] = round(n_routes / (full_p50 / 1e3), 1)

    # hop-count metric regime (Open/R's DEFAULT: all link metrics
    # equal): same topology and table shapes — the same compiled
    # kernel, no recompile — but the sweep loop converges in
    # ~graph-diameter sweeps (~5-8) instead of the ~19-24 the 1..64
    # metric range needs (docs/spf_kernel_profile.md §2; the regime
    # the <10 ms north star is reachable in)
    ls_h, _ps_h, csr_h = erdos_renyi_lsdb(
        N_NODES, avg_degree=AVG_DEGREE, seed=0, max_metric=1
    )
    uniform_before = tpu.spf_kernel_stats["uniform_metric"]
    # table upload + warm run — same table shapes as the headline, so
    # `compiles` here MUST come out 0 (any recompile is a bucket leak)
    _compiled("hop-metric-regime", lambda: tpu.solve(ls_h, "node-0"))
    hop_times = []
    for _ in range(max(3, iters // 2)):
        t0 = time.perf_counter()
        tpu.solve(ls_h, "node-0")
        hop_times.append((time.perf_counter() - t0) * 1e3)
    hop_p50, hop_p99 = _p50_p99(hop_times)
    detail["hop_metric_solve_ms"] = round(hop_p50, 3)
    detail["hop_metric_solve_p99_ms"] = round(hop_p99, 3)
    # attest detection for THIS topology (delta, not the cumulative
    # counter — an earlier uniform-metric section would mask a miss)
    detail["hop_metric_regime_detected"] = (
        tpu.spf_kernel_stats["uniform_metric"] > uniform_before
    )

    # BASELINE config 3's own metric (sources/sec on the all-sources
    # shape): the gather-bound relax costs the same per sweep for B=256
    # as for B=32, so the batch amortizes — measure it directly
    b256 = np.arange(256, dtype=np.int32) % csr.num_nodes

    def _b256_warm():  # compile + run, drained so the compile is paid here
        warm = tpu._solve_dist(csr, b256)
        float(np.asarray(warm[:, 0]).sum())

    _compiled("b256-all-sources", _b256_warm)
    b256_times = []
    for _ in range(3):  # p50-of-3: one slow reading must not move the row
        t0 = time.perf_counter()
        d256 = tpu._solve_dist(csr, b256)
        float(np.asarray(d256[:, 0]).sum())  # force completion
        b256_times.append((time.perf_counter() - t0) * 1e3)
    b256_ms = float(np.percentile(b256_times, 50))
    detail["tpu_b256_solve_ms"] = round(b256_ms, 3)
    detail["tpu_b256_sources_per_sec"] = round(256 / (b256_ms / 1e3), 1)

    # trace-derived convergence: full-stack emulator link-downs measured
    # through the PerfEvents pipeline (spark→fib per-stage markers), the
    # operator metric DeltaPath argues for — NOT a wall-clock guess.
    # Runs on the CPU oracle backend: a host-path number, not a device
    # one.
    from openr_tpu.emulator import measure_convergence

    conv = measure_convergence(trials=2)
    detail["convergence"] = conv

    # prefix-only churn: the dirty-scoped rebuild pipeline's headline
    # (skip-SPF on prefix churn). Runs on the host-side oracle engine —
    # the scoped path skips solves identically on both engines; the
    # forced-full run of the SAME workload gives the speedup the scoped
    # pipeline buys.
    from benchmarks.bench_churn import measure_prefix_churn

    pchurn = measure_prefix_churn(nodes=80, rounds=60, solver="cpu")
    pchurn_full = measure_prefix_churn(
        nodes=80, rounds=20, solver="cpu", force_full=True
    )
    detail["prefix_churn"] = {
        "scoped": pchurn,
        "forced_full_p50_ms": pchurn_full["prefix_churn_p50_ms"],
        "speedup_vs_full": round(
            pchurn_full["prefix_churn_p50_ms"]
            / max(pchurn["prefix_churn_p50_ms"], 1e-6),
            1,
        ),
    }

    # topo churn: the topology-delta warm-start pipeline's headline
    # (REBUILD_TOPO_DELTA — bounded recompute on link flap / metric
    # change). Host-side oracle engine, like the stage above.
    from benchmarks.bench_churn import measure_topo_churn

    tchurn = measure_topo_churn(nodes=80, rounds=40, solver="cpu")
    tchurn_full = measure_topo_churn(
        nodes=80, rounds=15, solver="cpu", force_full=True
    )
    detail["topo_churn"] = {
        "warm": tchurn,
        "forced_full_p50_ms": tchurn_full["topo_churn_p50_ms"],
        "speedup_vs_full": round(
            tchurn_full["topo_churn_p50_ms"]
            / max(tchurn["topo_churn_p50_ms"], 1e-6),
            1,
        ),
    }

    # million-prefix data plane: the prefix ramp through solve →
    # vectorized election → RIB → group-aware diff → delta FIB
    # programming (benchmarks/bench_prefix_scale.py). Host-dominated —
    # the solve graph is small.
    from benchmarks.bench_prefix_scale import measure_prefix_ramp

    counts_env = os.environ.get("OPENR_BENCH_PREFIX_COUNTS")
    if counts_env:
        counts = tuple(int(x) for x in counts_env.split(","))
    else:
        counts = (10_000, 100_000, 1_000_000)
    detail["prefix_scale"] = measure_prefix_ramp(
        prefix_counts=counts, nodes=2048, iters=3
    )

    detail["iters"] = iters  # device/platform recorded at graph-build
    out = {
        "metric": METRIC_NAME,
        "value": round(solve_p50, 3),
        "unit": "ms",
        "vs_baseline": round(TARGET_MS / solve_p50, 4),
        "convergence_p50_ms": conv.get("convergence_p50_ms"),
        # hop-span-derived per-stage p50 breakdown of the same traces
        # (docs/Monitor.md "Flood tracing") — the attributable scaling
        # curve's per-point decomposition, carried from day one
        "convergence_attribution": conv.get("convergence_attribution"),
        "prefix_churn_p50_ms": pchurn.get("prefix_churn_p50_ms"),
        "topo_churn_p50_ms": tchurn.get("topo_churn_p50_ms"),
        # largest completed prefix-ramp rung's end-to-end throughput
        "prefix_routes_per_sec": (
            detail.get("prefix_scale", {}).get("rungs") or [{}]
        )[-1].get("routes_per_sec"),
    }
    out["detail"] = detail
    print(json.dumps(out))

    # bench-history sentinel (benchmarks/history.py): append this run's
    # row plus the compile-ledger and kernel-cost snapshots keyed by
    # host fingerprint, then warn when a headline metric drifted >25%
    # vs the median of prior same-fingerprint runs. Best-effort: a
    # read-only checkout must never fail the measurement.
    try:
        from benchmarks.history import (
            append_row,
            check_history,
            load_history,
        )
        from openr_tpu.monitor import device as device_telemetry

        append_row(
            out,
            compiles=led.snapshot().per_fn,
            kernel_cost={
                k: r.to_jsonable()
                for k, r in device_telemetry.kernel_rows().items()
            },
        )
        for w in check_history(load_history()):
            print(f"# bench-history REGRESSION: {w}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — history must never fail a run
        print(f"# bench-history unavailable: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
