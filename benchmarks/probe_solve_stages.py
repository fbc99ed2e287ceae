"""Per-stage timing of TpuSpfSolver.solve's fused split path at 100k.

The pre-PR-1 chip decomposition (logs removed in PR 21) shows
pure kernel p50 206 ms but the headline solve p50 335 ms; this probe
splits the remaining ~130 ms between: host prep (to_csr, neighbor
metric scan), the fused dispatch + scalar drain, the packed-buffer
device→host transfer, and unpack_rib_buffer.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.common.constants import METRIC_MAX
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.ops.spf import pad_batch
from openr_tpu.ops.spf_split import batched_sssp_split_rib, unpack_rib_buffer
from openr_tpu.utils.topogen import erdos_renyi_lsdb

N = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
ITERS = int(os.environ.get("STAGE_ITERS", "8"))

print(f"# device: {jax.devices()[0].device_kind}", flush=True)
ls, ps, csr = erdos_renyi_lsdb(N, avg_degree=22, seed=0, max_metric=64)
tpu = TpuSpfSolver(native_rib="off")

# warm everything once through the public entry
tpu.solve(ls, "node-0")


def p50(xs):
    return float(np.percentile(xs, 50))


rows: dict[str, list[float]] = {}


def rec(k, ms):
    rows.setdefault(k, []).append(ms)


for it in range(ITERS):
    t0 = time.perf_counter()
    csr = ls.to_csr()
    my_id = csr.name_to_id["node-0"]
    nbr_ids = sorted(d for (s, d) in csr.adj_details if s == my_id)
    n = len(nbr_ids)
    b = pad_batch(1 + n)
    nbr_metric_real = np.empty(n, dtype=np.int32)
    for i, d in enumerate(nbr_ids):
        nbr_metric_real[i] = min(
            min(det[1] for det in csr.details(my_id, d)), METRIC_MAX
        )
    dead = tpu.solve_vp(csr) - 1
    nbr_ids_p = np.full(b - 1, dead, dtype=np.int32)
    nbr_ids_p[:n] = nbr_ids
    nbr_metric = np.full(b - 1, METRIC_MAX, dtype=np.int32)
    nbr_metric[:n] = nbr_metric_real
    nbr_over = np.ones(b - 1, dtype=bool)
    nbr_over[:n] = csr.node_overloaded[np.array(nbr_ids, dtype=np.int64)]
    roots = np.full(b, my_id, dtype=np.int32)
    roots[1 : 1 + n] = nbr_ids
    dev, has_over = tpu._dispatch(csr)
    vp = dev["vp"]
    gs = tpu._pick_gs_and_count(dev)
    t1 = time.perf_counter()
    rec("host prep (to_csr, nbrs, dispatch)", (t1 - t0) * 1e3)

    dist_dev, packed = batched_sssp_split_rib(
        dev["base_nbr"], dev["base_wgt"], dev["ov_ids"], dev["ov_nbr"],
        dev["ov_wgt"], dev["out_nbr"], dev["over"], jnp.asarray(roots),
        jnp.asarray(nbr_metric), jnp.asarray(nbr_ids_p),
        jnp.asarray(nbr_over), jnp.int32(my_id),
        has_overloads=has_over, with_lfa=tpu.enable_lfa, gs_chunks=gs,
    )
    jax.block_until_ready(packed)
    t2 = time.perf_counter()
    rec("fused dispatch + block_until_ready", (t2 - t1) * 1e3)

    buf = np.asarray(packed)
    t3 = time.perf_counter()
    rec(f"packed transfer ({buf.nbytes / 1e6:.2f} MB)", (t3 - t2) * 1e3)

    d_root, fh, lfa = unpack_rib_buffer(buf, vp, b, tpu.enable_lfa)
    t4 = time.perf_counter()
    rec("unpack_rib_buffer", (t4 - t3) * 1e3)
    rec("TOTAL", (t4 - t0) * 1e3)

for k, xs in rows.items():
    print(f"  {k:42s} p50 {p50(xs):9.2f} ms  (min {min(xs):.2f})", flush=True)
