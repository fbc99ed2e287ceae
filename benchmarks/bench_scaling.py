"""Strong-scaling table for `sharded_sssp_split` on the virtual CPU mesh.

Usage:  python benchmarks/bench_scaling.py [n_nodes] [batch]

Measures the FLAGSHIP sharded solve (parallel/sharded_spf.py) at mesh
sizes 1/2/4/8 in both factorization families on one fixed graph:

  * sources-only  (S×1): roots sharded, no in-sweep collective;
  * graph-sharded (1×G): table rows sharded, one tiled all_gather per
    sweep over the graph axis (the ICI frontier exchange).

HONESTY NOTE (printed into the output): this host has ONE physical
core, and `--xla_force_host_platform_device_count` devices are threads
sharing it — wall-clock here CANNOT show parallel speedup. What the
table DOES measure is (a) correctness of every mesh program at every
size (each factorization is a different SPMD program), and (b) the
*sharding overhead*: wall(N devices) / wall(1 device) with compute
serialized is exactly the partition + collective overhead factor the
real-chip speedup has to beat. The v5e-4 projection combines that
overhead with the measured single-chip sweep rate (docs/
spf_kernel_profile.md) — see docs/scaling.md for the derivation.

Each row: mesh, wall p50 of 3 warm solves, per-device gathered rows per
sweep (the quantity that scales), bytes all-gathered per sweep.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

N_DEV = 8
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={N_DEV}"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from openr_tpu.ops.spf_split import build_split_tables  # noqa: E402
from openr_tpu.parallel import make_mesh, sharded_sssp_split  # noqa: E402
from openr_tpu.utils import topogen  # noqa: E402


def main() -> None:
    n_nodes = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    b = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    devs = jax.devices("cpu")
    assert len(devs) >= N_DEV, devs

    es, ed, em, _vp, nn, ne = topogen.erdos_renyi_csr(
        n_nodes, avg_degree=20, seed=0, max_metric=64
    )
    t = build_split_tables(es, ed, em, nn)
    vp, w = t["base_nbr"].shape
    args = (
        jnp.asarray(t["base_nbr"]), jnp.asarray(t["base_wgt"]),
        jnp.asarray(t["ov_ids"]), jnp.asarray(t["ov_nbr"]),
        jnp.asarray(t["ov_wgt"]), jnp.asarray(np.zeros(vp, bool)),
    )
    roots = jnp.asarray(np.arange(b, dtype=np.int32) % nn)
    print(
        f"# host cores: {os.cpu_count()} — virtual devices share them; "
        "wall ratios measure SHARDING OVERHEAD, not speedup (see "
        "module docstring)"
    )
    print(f"# graph: {nn} nodes / {ne} directed edges, vp={vp}, "
          f"W={w}, B={b}")

    rows = []
    meshes = [("sources", s, 1) for s in (1, 2, 4, 8) if b % s == 0]
    meshes += [("graph", 1, g) for g in (2, 4, 8) if vp % g == 0]
    ref = None
    for fam, s, g in meshes:
        mesh = make_mesh(n_sources=s, n_graph=g, devices=devs[: s * g])
        def solve():
            return sharded_sssp_split(*args, roots, mesh)
        d = np.asarray(solve())  # compile + run
        if ref is None:
            ref = d
        else:
            assert (d == ref).all(), f"mesh {s}x{g} distances diverge"
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(solve())
            times.append((time.perf_counter() - t0) * 1e3)
        times.sort()
        p50 = times[1]
        per_dev_rows = vp // g * w
        gathered_mb = (
            0.0 if g == 1 else vp * (b // s) * 4 / 1e6
        )  # all_gather output per sweep per device
        rows.append({
            "mesh": f"{s}x{g}", "family": fam, "devices": s * g,
            "wall_p50_ms": round(p50, 1),
            "per_dev_gather_rows_per_sweep": per_dev_rows,
            "allgather_mb_per_sweep": round(gathered_mb, 2),
        })
        print(json.dumps(rows[-1]), flush=True)

    base = next(r for r in rows if r["devices"] == 1)
    print("\n| mesh | devices | wall p50 (ms) | vs 1-dev | per-dev gather "
          "rows/sweep | all-gather MB/sweep |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['mesh']} ({r['family']}) | {r['devices']} | "
            f"{r['wall_p50_ms']} | "
            f"{r['wall_p50_ms'] / base['wall_p50_ms']:.2f}x | "
            f"{r['per_dev_gather_rows_per_sweep']:,} | "
            f"{r['allgather_mb_per_sweep']} |"
        )


if __name__ == "__main__":
    main()
