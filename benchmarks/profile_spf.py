"""Decompose the headline SPF kernel time on real hardware.

The r2 dense kernel took 619 ms p50 with no profile. This harness answers:
  (a) how many relax sweeps does the 100k-node solve run?
  (b) what does ONE sweep of the XLA dense relax cost (ms, implied GB/s)?
  (c) does the Pallas VMEM kernel compile/run on the real chip, and what
      does one of its sweeps cost?
  (d) where does the time go (jax.profiler trace, optional)?

Run:  python benchmarks/profile_spf.py [--trace /tmp/spf_trace]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

N_NODES = 100_000
AVG_DEGREE = 20


def sync(x) -> float:
    """Force device completion by fetching a scalar."""
    return float(x)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None, help="xprof trace dir")
    ap.add_argument("--nodes", type=int, default=N_NODES)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--skip-pallas", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from openr_tpu.ops.spf import (
        INF_DIST,
        batched_sssp_dense,
        build_dense_tables,
        pad_batch,
    )
    from openr_tpu.utils import topogen

    dev = jax.devices()[0]
    print(f"# device: {dev} platform={dev.platform}")

    edge_src, edge_dst, edge_metric, vp, n, e = topogen.erdos_renyi_csr(
        args.nodes, avg_degree=AVG_DEGREE, seed=0, max_metric=64
    )
    nbr, wgt = build_dense_tables(edge_src, edge_dst, edge_metric, vp)
    print(f"# graph: V={n} (padded {vp}) E={e} D={nbr.shape[1]}")

    me = 0
    valid = edge_metric < int(INF_DIST)
    nbrs = np.unique(edge_dst[(edge_src == me) & valid])
    b = pad_batch(min(1 + len(nbrs), args.batch))
    roots = np.full(b, me, dtype=np.int32)
    roots[1 : 1 + min(len(nbrs), b - 1)] = nbrs[: b - 1]

    d_nbr = jnp.asarray(nbr)
    d_wgt = jnp.asarray(wgt)
    d_over = jnp.asarray(np.zeros(vp, dtype=bool))
    d_roots = jnp.asarray(roots)

    # ---- (a) sweep count ------------------------------------------------
    @jax.jit
    def solve_with_iters(roots):
        num_nodes = d_nbr.shape[0]
        bb = roots.shape[0]
        dist = jnp.full((num_nodes, bb), INF_DIST, jnp.int32)
        dist = dist.at[roots, jnp.arange(bb)].set(0)

        def relax(state):
            dist, _c, it = state
            d = dist[d_nbr]
            cand = jnp.where(
                d < INF_DIST,
                jnp.minimum(d + d_wgt[:, :, None], INF_DIST),
                INF_DIST,
            )
            new = jnp.minimum(cand.min(axis=1), dist)
            return new, jnp.any(new < dist), it + 1

        def cond(state):
            return state[1] & (state[2] < num_nodes)

        dist, _, iters = jax.lax.while_loop(
            cond, relax, (dist, jnp.bool_(True), 0)
        )
        return dist.sum(), iters

    t0 = time.perf_counter()
    s, iters = solve_with_iters(d_roots)
    s = sync(s)
    compile_and_run = time.perf_counter() - t0
    iters = int(iters)
    print(f"# sweeps to fixpoint: {iters} (first run incl compile: "
          f"{compile_and_run*1e3:.0f} ms)")

    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        s, _ = solve_with_iters(d_roots)
        sync(s)
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    full_ms = times[len(times) // 2]
    print(f"# full solve (while_loop): p50 {full_ms:.1f} ms over 5")

    # ---- (b) one XLA sweep ---------------------------------------------
    @jax.jit
    def one_sweep(dist):
        d = dist[d_nbr]
        cand = jnp.where(
            d < INF_DIST,
            jnp.minimum(d + d_wgt[:, :, None], INF_DIST),
            INF_DIST,
        )
        new = jnp.minimum(cand.min(axis=1), dist)
        return new

    dist0 = jnp.full((vp, b), np.int32(INF_DIST), jnp.int32)
    dist0 = dist0.at[d_roots, jnp.arange(b)].set(0)
    w = one_sweep(dist0)
    sync(w.sum())
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        w = one_sweep(dist0)
        sync(w.sum())
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    sweep_ms = times[len(times) // 2]
    gathered_bytes = vp * nbr.shape[1] * b * 4
    print(
        f"# one XLA dense sweep: p50 {sweep_ms:.2f} ms "
        f"(gather output {gathered_bytes/1e9:.2f} GB → "
        f"{gathered_bytes/1e9/(sweep_ms/1e3):.0f} GB/s implied)"
    )
    print(f"# sweeps×sweep = {iters * sweep_ms:.1f} ms vs full {full_ms:.1f}")

    # ---- (c) pallas sweep ----------------------------------------------
    if not args.skip_pallas:
        try:
            from openr_tpu.ops.spf_pallas import _relax_once, pick_tile

            tile = pick_tile(vp, b, nbr.shape[1], want=256)
            print(f"# pallas tile: {tile}")
            over_t = jnp.zeros_like(d_nbr, dtype=bool)
            t0 = time.perf_counter()
            nd, ch = _relax_once(
                d_nbr, d_wgt, over_t, d_roots, dist0, tile, False, False
            )
            sync(ch)
            print(f"# pallas compile+run: {(time.perf_counter()-t0)*1e3:.0f} ms")
            # correctness vs XLA sweep
            ok = bool((nd == w).all())
            print(f"# pallas sweep == xla sweep: {ok}")
            times = []
            for _ in range(10):
                t0 = time.perf_counter()
                nd, ch = _relax_once(
                    d_nbr, d_wgt, over_t, d_roots, dist0, tile, False, False
                )
                sync(ch)
                times.append((time.perf_counter() - t0) * 1e3)
            times.sort()
            p_ms = times[len(times) // 2]
            print(
                f"# one pallas sweep: p50 {p_ms:.2f} ms "
                f"({gathered_bytes/1e9/(p_ms/1e3):.0f} GB/s implied)"
            )
        except Exception as ex:  # noqa: BLE001
            print(f"# pallas FAILED: {type(ex).__name__}: "
                  f"{str(ex).splitlines()[0][:300]}")

    # ---- (d) trace ------------------------------------------------------
    if args.trace:
        with jax.profiler.trace(args.trace):
            for _ in range(3):
                s, _ = solve_with_iters(d_roots)
                sync(s)
        print(f"# trace written to {args.trace}")


if __name__ == "__main__":
    main()
