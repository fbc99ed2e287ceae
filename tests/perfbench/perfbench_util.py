"""Shared by the perfbench tests: a throw-away checkout whose cells run the
rehearsal configurations, and a subprocess runner for run.py."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: cell name -> (rehearsal configuration, traffic mix)
TINY_CELLS = {
    "tiny_fabric.metric_flap": ("tiny_fabric", "metric_flap"),
    "tiny_er.full_rib": ("tiny_er", "full_rib"),
}


def load_benchmark() -> dict:
    with open(REPO / "BENCHMARK.json") as f:
        return json.load(f)


def tiny_checkout(tmp: Path, with_program: bool = True) -> Path:
    """A copy of perfbench/ beside a BENCHMARK.json whose cells are the real
    ones re-pointed at the rehearsal configurations; the program is linked
    in, not copied."""
    root = tmp / "checkout"
    root.mkdir()
    shutil.copytree(
        REPO / "perfbench", root / "perfbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    if with_program:
        os.symlink(REPO / "openr_tpu", root / "openr_tpu")
        os.symlink(REPO / "native", root / "native")
    bench = load_benchmark()
    real = {w["traffic"]: w["name"] for w in bench["workloads"]}
    rename = {}
    bench["configs"] = []
    bench["workloads"] = []
    for cell, (config, traffic) in TINY_CELLS.items():
        bench["configs"].append({
            "name": config, "source": "rehearsal",
            "file": f"perfbench/configs/{config}.json", "reduced": [],
            "why": "rehearsal",
        })
        bench["workloads"].append({
            "name": cell, "config": config, "traffic": traffic, "chips": 1,
            "why": "rehearsal",
        })
        rename[real[traffic]] = cell
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    write_benchmark(root, bench)
    return root


def write_benchmark(root: Path, bench: dict) -> None:
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)


def run_py(root: Path, *args: str, timeout: int = 300):
    """`python perfbench/run.py ...` from `root`, on the CPU, with a compile
    cache of its own so that the repo's is left alone."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *args], cwd=root, env=env,
        timeout=timeout, capture_output=True, text=True,
    )


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
