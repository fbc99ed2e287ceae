"""Step-mode churn rebuild profile (BASELINE config 5 protocol note).

The live harness (bench_churn.py) measures flap→RIB latency through the
real event loop — which on a 1-core bench host makes the RECOMPUTE
numbers move ~2x with host weather, because the flap generator, the
drainer and the solver thread all contend for the same core (round-3
verdict). This harness isolates the recompute pipeline: flaps are
pre-generated, then injected in fixed-size batches and the rebuild body
(decode → apply+snapshot → compute+diff) is driven SYNCHRONOUSLY and
timed per stage — no event loop, no generator contention, no timer
jitter. This is the protocol for the config-5 "steady-state recompute"
row; the live harness remains the protocol for flap→RIB latency.

Usage: python benchmarks/profile_churn_rebuild.py [--nodes 1280]
         [--flaps-per-cycle 40] [--cycles 50] [--profile]
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
    ap.add_argument("--nodes", type=int, default=1280)
    ap.add_argument("--flaps-per-cycle", type=int, default=40)
    ap.add_argument("--cycles", type=int, default=50)
    ap.add_argument(
        "--profile", action="store_true",
        help="cProfile the compute+diff stage and print the top 25",
    )
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"  # host profile; before jax import

    import dataclasses

    from benchmarks.bench_churn import build_decision
    from openr_tpu.utils import topogen

    k = max(4, int(round((args.nodes * 4 / 5) ** 0.5 / 2)) * 2)
    adj_dbs, prefix_dbs = topogen.fat_tree(k, metric=10)
    dec, pubs, routes, pub_for = build_decision(adj_dbs, prefix_dbs)

    # first full rebuild (compile + cold caches) outside the timing
    dec._drain_pending()
    states = dec._snapshot_states()
    dec.rib, _ = dec._compute_and_diff(states)

    rng = np.random.default_rng(7)
    adj_dbs = list(adj_dbs)
    versions = {db.this_node_name: 1 for db in adj_dbs}
    warm_cycles = 3
    total = args.flaps_per_cycle * (args.cycles + warm_cycles)
    pregen = []
    for _ in range(total):
        i = int(rng.integers(0, len(adj_dbs)))
        db = adj_dbs[i]
        j = int(rng.integers(0, len(db.adjacencies)))
        new_adjs = list(db.adjacencies)
        a = new_adjs[j]
        new_adjs[j] = dataclasses.replace(
            a, metric=int(rng.integers(1, 64))
        )
        db = dataclasses.replace(db, adjacencies=tuple(new_adjs))
        adj_dbs[i] = db
        versions[db.this_node_name] += 1
        pregen.append(pub_for(db, version=versions[db.this_node_name]))

    stages: dict[str, list[float]] = {
        "decode": [], "apply_snapshot": [], "compute_diff": [],
        "total": [],
    }
    prof = None
    if args.profile:
        import cProfile

        prof = cProfile.Profile()
    # warm cycles so caches (entry/class dicts) reach steady state
    n = 0
    for cyc in range(args.cycles + warm_cycles):
        for _ in range(args.flaps_per_cycle):
            if n >= total:
                break
            dec.process_publication(pregen[n])
            n += 1
        t0 = time.perf_counter()
        batch = dict(dec._pending_kvs)
        decoded = dec._decode_batch(batch)
        t1 = time.perf_counter()
        dec._drain_pending(decoded)
        states = dec._snapshot_states()
        t2 = time.perf_counter()
        if prof is not None and cyc >= warm_cycles:
            prof.enable()
        new_rib, update = dec._compute_and_diff(states)
        if prof is not None and cyc >= warm_cycles:
            prof.disable()
        t3 = time.perf_counter()
        dec.rib = new_rib
        if cyc < warm_cycles:
            continue
        stages["decode"].append((t1 - t0) * 1e3)
        stages["apply_snapshot"].append((t2 - t1) * 1e3)
        stages["compute_diff"].append((t3 - t2) * 1e3)
        stages["total"].append((t3 - t0) * 1e3)

    out = {
        "metric": "churn_stepmode_recompute_p50_ms",
        "value": round(float(np.percentile(stages["total"], 50)), 2),
        "unit": "ms",
        "vs_baseline": None,
        "detail": {
            "config": 5,
            "protocol": "step-mode (synchronous rebuild; no event loop)",
            "nodes": len(adj_dbs),
            "flaps_per_cycle": args.flaps_per_cycle,
            "cycles": args.cycles,
            "p99_ms": round(float(np.percentile(stages["total"], 99)), 2),
            "stage_p50_ms": {
                kk: round(float(np.percentile(v, 50)), 2)
                for kk, v in stages.items()
            },
            "decode_stats": dict(dec.decode_stats),
        },
    }
    print(json.dumps(out))
    if prof is not None:
        import pstats

        pstats.Stats(prof).sort_stats("cumulative").print_stats(25)


if __name__ == "__main__":
    main()
