"""BASELINE config 4: backbone with KSP2_ED_ECMP SR prefixes + LFA.

Measures, on a 2-tier backbone (ring of rings — redundant paths so both
KSP2 and LFA produce real alternates):
  * full-RIB rebuild latency with enable_lfa on,
  * per-KSP2-prefix incremental cost (the masked host re-solve),
  * correctness: RIB equality vs the oracle with both features on.

Run: python benchmarks/bench_ksp_lfa.py [--rings 8] [--ring-size 16]
     [--ksp-frac 0.1] [--backend cpu]
Prints one JSON line (same contract as bench.py).

The measured form of config 4 is now the benchmark's configuration
`backbone_ksp` (`perfbench/`, PR 32), which the driver runs on the chip;
this script stays as one of D2's four older benchmarks.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402


def build_backbone(rings: int, ring_size: int):
    """Ring of rings: `rings` site-rings, adjacent sites joined by two
    parallel inter-site links (edge-disjoint paths everywhere)."""
    from openr_tpu.types.topology import (
        Adjacency,
        AdjacencyDatabase,
    )

    n = rings * ring_size
    edges: dict[tuple[int, int], int] = {}

    def add(a, b, m):
        edges[(a, b)] = m
        edges[(b, a)] = m

    for r in range(rings):
        base = r * ring_size
        for i in range(ring_size):
            add(base + i, base + (i + 1) % ring_size, 10)
        nxt = ((r + 1) % rings) * ring_size
        add(base, nxt, 100)  # inter-site
        add(base + ring_size // 2, nxt + ring_size // 2, 100)
    by_src: dict[int, list] = {}
    for (a, b), m in edges.items():
        by_src.setdefault(a, []).append((b, m))
    dbs = []
    for a in range(n):
        adjs = tuple(
            Adjacency(
                other_node_name=f"bb{b}", if_name=f"if{a}-{b}",
                other_if_name=f"if{b}-{a}", metric=m,
            )
            for b, m in sorted(by_src.get(a, []))
        )
        dbs.append(
            AdjacencyDatabase(
                this_node_name=f"bb{a}", adjacencies=adjs,
                node_label=100_000 + a,
            )
        )
    return dbs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rings", type=int, default=8)
    ap.add_argument("--ring-size", type=int, default=16)
    ap.add_argument("--ksp-frac", type=float, default=0.1)
    ap.add_argument("--ksp-k", type=int, default=16)  # BASELINE config 4
    ap.add_argument("--backend", choices=("auto", "cpu"), default="auto")
    args = ap.parse_args()
    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported

    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.oracle import compute_routes as oracle_routes
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import (
        ForwardingAlgorithm,
        ForwardingType,
        PrefixDatabase,
        PrefixEntry,
        PrefixMetrics,
    )

    dbs = build_backbone(args.rings, args.ring_size)
    n = len(dbs)
    rng = np.random.default_rng(0)
    ksp_nodes = set(
        rng.choice(n, size=max(1, int(n * args.ksp_frac)), replace=False)
        .tolist()
    )
    ls, ps = LinkState(), PrefixState()
    for d in dbs:
        ls.update_adjacency_db(d)
    for i in range(n):
        algo = (
            ForwardingAlgorithm.KSP2_ED_ECMP
            if i in ksp_nodes else ForwardingAlgorithm.SP_ECMP
        )
        ftype = (
            ForwardingType.SR_MPLS
            if i in ksp_nodes else ForwardingType.IP
        )
        ps.update_prefix_db(
            PrefixDatabase(
                this_node_name=f"bb{i}",
                prefix_entries=(
                    PrefixEntry(
                        prefix=IpPrefix.make(
                            f"10.{(i >> 8) & 255}.{i & 255}.0/24"
                        ),
                        metrics=PrefixMetrics(),
                        forwarding_type=ftype,
                        forwarding_algorithm=algo,
                    ),
                ),
            )
        )

    me = "bb1"
    solver = TpuSpfSolver(enable_lfa=True, ksp_k=args.ksp_k)
    rib = solver.compute_routes(ls, ps, me)  # warm (compile)
    ts = []
    for _ in range(10):
        t0 = time.perf_counter()
        rib = solver.compute_routes(ls, ps, me)
        ts.append((time.perf_counter() - t0) * 1e3)
    ts = np.array(ts)

    # correctness vs oracle, both features on
    ora = oracle_routes(ls, ps, me, enable_lfa=True, ksp_k=args.ksp_k)
    rib_diff = sum(
        1 for p in set(rib.unicast_routes) | set(ora.unicast_routes)
        if rib.unicast_routes.get(p) != ora.unicast_routes.get(p)
    )

    n_ksp = sum(
        1 for e in rib.unicast_routes.values()
        if e.best_entry is not None
        and e.best_entry.forwarding_algorithm
        == ForwardingAlgorithm.KSP2_ED_ECMP
    )
    n_backup = sum(
        1 for e in rib.unicast_routes.values() if e.backup_nexthops
    )
    # isolate per-KSP-prefix cost: rebuild with KSP prefixes flipped to
    # SP_ECMP and compare
    ps2 = PrefixState()
    for i in range(n):
        ps2.update_prefix_db(
            PrefixDatabase(
                this_node_name=f"bb{i}",
                prefix_entries=(
                    PrefixEntry(
                        prefix=IpPrefix.make(
                            f"10.{(i >> 8) & 255}.{i & 255}.0/24"
                        ),
                        metrics=PrefixMetrics(),
                    ),
                ),
            )
        )
    solver.compute_routes(ls, ps2, me)
    t0 = time.perf_counter()
    solver.compute_routes(ls, ps2, me)
    plain_ms = (time.perf_counter() - t0) * 1e3
    per_ksp_ms = max(0.0, (float(np.percentile(ts, 50)) - plain_ms)) / max(
        n_ksp, 1
    )

    import jax

    print(json.dumps({
        "metric": "ksp_lfa_full_rib_p50_ms",
        "value": round(float(np.percentile(ts, 50)), 3),
        "unit": "ms",
        "vs_baseline": None,
        "detail": {
            "config": 4,
            "nodes": n,
            "ksp_k": args.ksp_k,
            "ksp_prefixes": n_ksp,
            "routes_with_lfa_backups": n_backup,
            "p99_ms": round(float(np.percentile(ts, 99)), 3),
            "per_ksp_prefix_ms": round(per_ksp_ms, 3),
            "rib_diff_vs_oracle": rib_diff,
            "backend": jax.default_backend(),
        },
    }))


if __name__ == "__main__":
    main()
