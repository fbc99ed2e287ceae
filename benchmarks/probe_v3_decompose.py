"""Decompose the headline solve's 1047 ms on the real chip.

Separates: pure v3 kernel time (scalar materialization), dense-sweep
count vs tail behavior, first_hop_matrix dispatch, host transfer of the
[vp, B] distance matrix, and RIB assembly. Run on the TPU.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.ops.spf import first_hop_matrix
from openr_tpu.ops.spf_split import batched_sssp_split
from openr_tpu.utils.topogen import erdos_renyi_lsdb

N = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000

print(f"# device: {jax.devices()[0]}")
ls, ps, csr = erdos_renyi_lsdb(N, avg_degree=20, seed=0, max_metric=64)
tpu = TpuSpfSolver(native_rib="off")
dev = tpu._device_arrays(csr, "split")
vp = dev["base_nbr"].shape[0]
print(f"# vp={vp} W={dev['base_wgt'].shape[1]} Go={dev['ov_nbr'].shape[0]} "
      f"Wo={dev['ov_nbr'].shape[1]} Wout={dev['out_nbr'].shape[1]}")

my_id = csr.name_to_id["node-0"]
nbr_ids = csr.neighbor_ids(my_id)
b = 32
roots_h = np.full(b, my_id, dtype=np.int32)
roots_h[1 : 1 + len(nbr_ids)] = nbr_ids[: b - 1]
roots = jnp.asarray(roots_h)


def timeit(label, fn, n=5):
    fn()  # warm
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    print(f"  {label:45s} p50 {ts[len(ts)//2]:9.2f} ms  (min {ts[0]:.2f})")
    return ts[len(ts) // 2]


def solve():
    return batched_sssp_split(
        dev["base_nbr"], dev["base_wgt"], dev["ov_ids"], dev["ov_nbr"],
        dev["ov_wgt"], dev["out_nbr"], dev["over"], roots,
        has_overloads=False,
    )


# 1. pure kernel, scalar materialization
timeit("v3 solve B=32 (scalar drain)",
       lambda: float(jnp.asarray(solve()[0, 0])))

# 2. kernel + full host transfer of [vp, 32] i32
t_all = timeit("v3 solve B=32 + np.asarray full dist",
               lambda: np.asarray(solve()))

# 3. transfer alone (solve cached? no - rerun but transfer separately)
d = solve()
d.block_until_ready()
timeit("np.asarray([vp,32] i32) transfer only", lambda: np.asarray(d))
timeit("device_get via jax.device_get", lambda: jax.device_get(d))

# 4. first_hop_matrix dispatch on top
nbr_ids_p = np.full(b - 1, vp - 1, dtype=np.int32)
nbr_ids_p[: len(nbr_ids)] = nbr_ids[: b - 1]
nbr_metric = np.full(b - 1, 1, dtype=np.int32)
nbr_over = np.zeros(b - 1, dtype=bool)
fh_args = (jnp.asarray(nbr_metric), jnp.asarray(nbr_ids_p),
           jnp.asarray(nbr_over))
timeit("first_hop_matrix (on cached dist) + asarray",
       lambda: np.asarray(first_hop_matrix(d, *fh_args)))

# 5. sweep-count diagnostics: dense-only variants via tail knobs
for thr in (0, 1024, 8192, 32768):
    def run(thr=thr):
        out = batched_sssp_split(
            dev["base_nbr"], dev["base_wgt"], dev["ov_ids"], dev["ov_nbr"],
            dev["ov_wgt"], dev["out_nbr"], dev["over"], roots,
            has_overloads=False, tail_threshold=thr,
            tail_cap=max(8192, thr * 2), tail_rounds_cap=64,
        )
        return float(jnp.asarray(out[0, 0]))
    timeit(f"v3 solve tail_threshold={thr}", run, n=3)

# 6. per-sweep cost: K extra dense sweeps via a fori_loop probe
import functools


@functools.partial(jax.jit, static_argnames=("k",))
def dense_k(dist0, k):
    def sweep(_, dist):
        g = dist[dev["base_nbr"]]
        cand = jnp.where(
            g < np.int32(1 << 30),
            jnp.minimum(g + dev["base_wgt"][:, :, None], np.int32(1 << 30)),
            np.int32(1 << 30),
        )
        return jnp.minimum(cand.min(axis=1), dist)
    return jax.lax.fori_loop(0, k, sweep, dist0)


dist0 = jnp.full((vp, b), np.int32(1 << 30), jnp.int32)
dist0 = dist0.at[roots, jnp.arange(b)].set(0)
t1 = timeit("dense sweeps k=1", lambda: float(jnp.asarray(
    dense_k(dist0, 1)[0, 0])), n=3)
t13 = timeit("dense sweeps k=13", lambda: float(jnp.asarray(
    dense_k(dist0, 13)[0, 0])), n=3)
per = (t13 - t1) / 12
rows = vp * dev["base_wgt"].shape[1]
print(f"  -> per-sweep {per:.2f} ms, {rows/1e6:.2f} M rows/sweep, "
      f"{rows/per/1e6:.3f} G rows/s")
