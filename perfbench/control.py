#!/usr/bin/env python3
"""perfbench/control.py — the control of the comparison, at a cell's own size.

The comparison that decides `correct` is exact (limit 0), so what has to be
shown is that it can fail: the reference itself, put in the program's place
with one stated guarantee broken, has to come out as not correct. The
control breaks ECMP: where several next hops tie it keeps one.

    python3 perfbench/control.py --workload fabric10k.metric_flap --seeds 1+2+3

For each seed: the cell's graph after `--events` of the mix's link changes
drawn from the seed, the reference's tables, the control's tables, and the
numbers the harness compares. Host work only (no JAX); run it on the chip's
machine all the same, at the size the cell runs at. Exit 0 when the control
failed the comparison on every seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main() -> int:
    from perfbench import compare, reference, topo
    from perfbench.events import draw_link, flap_sequence, link_pool, root_of
    from perfbench.run import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="seed+seed+...")
    ap.add_argument("--events", type=int, default=150)
    args = ap.parse_args()
    found = load_cell(ROOT, args.workload)
    config, traffic = found["config"], found["traffic"]
    failed_everywhere = True
    for seed in map(int, args.seeds.split("+")):
        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        g = topo.build(config["topology"])
        root = root_of(g, config["root"])
        pool = link_pool(g, traffic["links"], root)
        if "raised_metric" in traffic:
            flaps = flap_sequence(pool, rng, traffic)
            changes = [next(flaps) for _ in range(args.events)]
        else:
            lo, hi = traffic["metric_range"]
            changes = [(draw_link(pool, rng), int(rng.integers(lo, hi + 1)))
                       for _ in range(args.events)]
        for (u, v), metric in changes:
            g.set_metric(u, v, metric)
        want_u, want_m = reference.tables(g, root)
        ctl_u, ctl_m = reference.tables(g, root, ecmp=False)
        nu, _ = compare.count_differences(ctl_u, want_u)
        nm, _ = compare.count_differences(ctl_m, want_m)
        row = {
            "workload": args.workload, "seed": seed, "events": args.events,
            "control": "ecmp broken (one next hop where several tie)",
            "unicast_routes_differ": {"value": nu, "limit": 0, "of": len(want_u)},
            "mpls_routes_differ": {"value": nm, "limit": 0, "of": len(want_m)},
            "correct": nu == 0 and nm == 0,
            "seconds": round(time.perf_counter() - t0, 1),
        }
        failed_everywhere &= not row["correct"]
        print(json.dumps(row), flush=True)
    return 0 if failed_everywhere else 1


if __name__ == "__main__":
    sys.exit(main())
