"""Fleet RIB rebuild: all nodes' routes from one batched device solve.

BASELINE configs 1-2 measure one node's rebuild; an emulator (or any
what-if analysis over a fabric) needs EVERY node's RIB. The reference
shape is N sequential solver runs; the TPU shape is one batched solve
(decision/fleet.py) + N host assemblies. This harness reports both, so
the batch amortization is a measured number rather than a claim.

Run: python benchmarks/bench_fleet.py [--k 16] [--backend cpu]
Prints one JSON line (same contract as bench.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=16, help="fat-tree k")
    ap.add_argument("--sample", type=int, default=8,
                    help="per-node solver sample size for the baseline")
    ap.add_argument("--backend", choices=("auto", "cpu"), default="auto")
    args = ap.parse_args()
    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported

    from openr_tpu.decision.fleet import compute_fleet_ribs
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.utils import topogen

    adj_dbs, prefix_dbs = topogen.fat_tree(args.k, metric=10)
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    n = len(adj_dbs)

    solver = TpuSpfSolver(native_rib="off")
    compute_fleet_ribs(ls, ps, nodes=[ls.nodes[0]], solver=solver)  # warm

    t0 = time.perf_counter()
    fleet = compute_fleet_ribs(ls, ps, solver=solver)
    fleet_ms = (time.perf_counter() - t0) * 1e3
    n_routes = sum(
        len(r.unicast_routes) + len(r.mpls_routes) for r in fleet.values()
    )

    # per-node baseline (sampled): the reference shape — one solver run
    # per node
    rng = np.random.default_rng(0)
    sample = [
        ls.nodes[i]
        for i in rng.choice(n, size=min(args.sample, n), replace=False)
    ]
    per = TpuSpfSolver(native_rib="off")
    for node in sample:  # warm EVERY sampled batch shape (degree
        per.compute_routes(ls, ps, node)  # classes jit separately)
    t0 = time.perf_counter()
    for node in sample:
        per.compute_routes(ls, ps, node)
    per_node_ms = (time.perf_counter() - t0) * 1e3 / len(sample)

    print(
        json.dumps(
            {
                "metric": "fleet_full_rib_rebuild_ms",
                "value": round(fleet_ms, 3),
                "unit": "ms",
                "vs_baseline": round(per_node_ms * n / fleet_ms, 2),
                "detail": {
                    "nodes": n,
                    "routes": n_routes,
                    "routes_per_sec": round(
                        n_routes / (fleet_ms / 1e3), 1
                    ),
                    "per_node_solver_ms": round(per_node_ms, 3),
                    "per_node_extrapolated_ms": round(per_node_ms * n, 1),
                    "speedup_vs_per_node": round(
                        per_node_ms * n / fleet_ms, 2
                    ),
                    "backend": _backend(),
                },
            }
        )
    )


def _backend() -> str:
    import jax

    return jax.default_backend()


if __name__ == "__main__":
    main()
