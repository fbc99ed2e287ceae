#!/usr/bin/env python3
"""perfbench/measure.py — several runs of run.py in one call, with spreads.

The builder's tool for a chip call (each process pays ~15 s to reach the
chip, so runs that share a compile go into one command):

    python3 perfbench/measure.py --tag flap_a \\
        fabric10k.metric_flap,20,0,101+102+103 er100k.full_rib,20,1,7

Each positional is cell,seconds,trace,seed+seed+...; runs are made one
after the other as child processes (this parent never imports JAX, so the
chip is free for each child). Every result line goes to
chiprun_out/<tag>.jsonl with the seed, exit code and wall time; the table
at the end gives each metric's values, median and spread (interquartile
range over median, `statistics.quantiles(n=4)`). `--copy-trace` copies the
last traced run's .xplane.pb to chiprun_out/.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values: list[float]) -> float | None:
    if len(values) < 2 or not statistics.median(values):
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", required=True)
    ap.add_argument("--copy-trace", action="store_true")
    ap.add_argument("specs", nargs="+")
    args = ap.parse_args()
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    log = out_dir / f"{args.tag}.jsonl"
    rows = []
    for spec in args.specs:
        cell, seconds, trace, seeds = spec.split(",")
        for seed in seeds.split("+"):
            cmd = [
                sys.executable, str(ROOT / "perfbench" / "run.py"),
                "--workload", cell, "--seed", seed,
                "--seconds", seconds, "--trace", trace,
            ]
            t0 = time.perf_counter()
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = proc.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except json.JSONDecodeError:
                result = None
            row = {
                "cell": cell, "seed": int(seed), "seconds": float(seconds),
                "trace": int(trace), "rc": proc.returncode,
                "wall_s": round(wall, 1), "result": result,
                "detail": [ln for ln in lines[:-1] if ln.startswith("[perfbench")],
            }
            if proc.returncode or result is None or not result.get("correct"):
                row["stderr_tail"] = proc.stderr[-3000:]
                row["stdout_tail"] = proc.stdout[-3000:]
            rows.append(row)
            with open(log, "a") as f:
                f.write(json.dumps(row) + "\n")
            ok = result is not None and result.get("correct")
            print(
                f"{cell} seed {seed} trace {trace}: rc {proc.returncode} "
                f"correct {ok} wall {wall:.1f}s "
                + (json.dumps({k: round(v["value"], 4) for k, v in
                               result["metrics"].items()}) if result else
                   proc.stderr[-1500:]),
                flush=True,
            )
    if args.copy_trace:
        traces = ROOT / ".perfbench_trace"
        for path in sorted(traces.rglob("*.xplane.pb")):
            cell = path.relative_to(traces).parts[0]
            dest = out_dir / f"{args.tag}.{cell}.xplane.pb"
            shutil.copy(path, dest)
            print(f"trace {path} -> {dest} ({dest.stat().st_size} bytes)")
    print("\nmetric: median, spread (IQR/median), first run apart")
    groups: dict[tuple, list] = {}
    for r in rows:
        if r["result"]:
            groups.setdefault((r["cell"], r["trace"], r["seconds"]), []).append(r)
    for (cell, trace, seconds), rs in groups.items():
        print(f"{cell} trace {trace} seconds {seconds}: {len(rs)} runs, "
              f"correct {sum(bool(r['result']['correct']) for r in rs)}")
        names = list(rs[0]["result"]["metrics"])
        for name in names:
            vals = [r["result"]["metrics"][name]["value"] for r in rs
                    if name in r["result"]["metrics"]]
            rest = vals[1:] if name == "setup_s" and len(vals) > 2 else vals
            sp = spread(rest)
            print(
                f"  {name}: median {statistics.median(rest):.4f} spread "
                f"{'n/a' if sp is None else f'{100 * sp:.2f}%'} "
                f"values {[round(v, 3) for v in vals]}"
            )
        dev = rs[-1]["result"]["device"]
        print(f"  device {dev}")
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
