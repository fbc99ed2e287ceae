"""Grid-search gather formulations for the SPF relax sweep (v5e).

The relax needs g[v,d,b] = dist[nbr[v,d], b] at VP*D rows/sweep. XLA's
gather measured ~0.1-0.35 Grows/s; this probe searches formulations for
a faster one. All probes K-iterate in-jit with data deps, so the
per-dispatch cost drops out of the per-iteration reading.
"""

from __future__ import annotations

import gc
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import functools

import jax
import jax.numpy as jnp
import numpy as np

rng = np.random.default_rng(0)
K = 12
VP = 100352  # 100k padded to multiple of 512 (not pow2 — 23% smaller)
D = 64
B = 32


def _leaf(out):
    return float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])


def timed(fn, *args, n=4):
    out = fn(*args)
    jax.block_until_ready(out)
    _leaf(out)
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        _leaf(out)
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def bench(name, make_body, init, rows):
    try:
        @functools.partial(jax.jit, static_argnames=("k",))
        def run(init, k):
            return jax.lax.fori_loop(0, k, lambda i, c: make_body(c), init)

        t1 = timed(lambda a: run(a, 1), init)
        tk = timed(lambda a: run(a, K), init)
        per = (tk - t1) / (K - 1)
        rate = rows / (per / 1e3) / 1e9 if per > 0.005 else float("inf")
        print(f"  {name:40s} per-sweep {per:8.2f} ms   {rate:6.3f} Grows/s")
    except Exception as e:  # noqa: BLE001
        lines = [l for l in str(e).splitlines() if l.strip()] or [repr(e)]
        print(f"  {name:40s} FAIL {lines[0][:140]}")
    finally:
        gc.collect()


print(f"# device: {jax.devices()[0]}  VP={VP} D={D} B={B}")

nbr_h = rng.integers(0, VP, size=(VP, D), dtype=np.int32)
wgt_h = rng.integers(1, 64, size=(VP, D), dtype=np.int32)
dist_h = rng.integers(0, 1 << 20, size=(VP, B), dtype=np.int32)
nbr = jnp.asarray(nbr_h)
wgt = jnp.asarray(wgt_h)
INF = np.int32(1 << 30)
ROWS = VP * D


def dep(new, dist):
    """Cheap data dep: keep iterating on new dist."""
    return jnp.minimum(new, dist)


# ---- A: current form: 2D-idx gather [VP, D] -> [VP, D, B] ----
def body_a(c):
    dist, = c
    g = dist[nbr]  # [VP, D, B]
    cand = jnp.minimum(g + wgt[:, :, None], INF)
    return (dep(cand.min(axis=1), dist),)


bench("A  2D-idx gather", body_a, (jnp.asarray(dist_h),), ROWS)


# ---- B: flat-idx gather ----
nbr_flat = jnp.asarray(nbr_h.reshape(-1))


def body_b(c):
    dist, = c
    g = dist[nbr_flat].reshape(VP, D, B)
    cand = jnp.minimum(g + wgt[:, :, None], INF)
    return (dep(cand.min(axis=1), dist),)


bench("B  flat-idx gather", body_b, (jnp.asarray(dist_h),), ROWS)


# ---- C: d-loop of 64 column gathers ----
def body_c(c):
    dist, = c
    acc = dist
    for d in range(D):
        g = dist[nbr[:, d]]  # [VP, B]
        acc = jnp.minimum(acc, g + wgt[:, d][:, None])
    return (acc,)


bench("C  d-loop 64 gathers", body_c, (jnp.asarray(dist_h),), ROWS)


# ---- D: chunked rows (8 chunks) ----
CH = 8


def body_d(c):
    dist, = c
    outs = []
    for i in range(CH):
        sl = slice(i * VP // CH, (i + 1) * VP // CH)
        g = dist[nbr[sl]]  # [VP/CH, D, B]
        cand = jnp.minimum(g + wgt[sl][:, :, None], INF)
        outs.append(cand.min(axis=1))
    return (dep(jnp.concatenate(outs, axis=0), dist),)


bench("D  8-chunk gather", body_d, (jnp.asarray(dist_h),), ROWS)


# ---- E: transposed table, lane gather ----
distT_h = np.ascontiguousarray(dist_h.T)  # [B, VP]


def body_e(c):
    distT, = c
    g = jnp.take(distT, nbr_flat, axis=1)  # [B, VP*D]
    g = g.reshape(B, VP, D)
    cand = jnp.minimum(g + wgt.T[None, :, :].transpose(0, 2, 1)[0][None], INF) if False else jnp.minimum(g + wgt[None, :, :], INF)
    new = cand.min(axis=2)  # [B, VP]
    return (jnp.minimum(new, distT),)


bench("E  lane-gather (T)", body_e, (jnp.asarray(distT_h),), ROWS)


# ---- F: i16 distances ----
dist16_h = (dist_h & 0x7FFF).astype(np.int16)


def body_f(c):
    dist, = c
    g = dist[nbr]
    cand = jnp.minimum(
        g.astype(jnp.int32) + wgt[:, :, None], np.int32(0x7FFF)
    ).astype(jnp.int16)
    return (dep(cand.min(axis=1), dist),)


bench("F  i16 gather", body_f, (jnp.asarray(dist16_h),), ROWS)


# ---- G: one-hot int8 MXU per src-block (128-wide), limb-split ----
# dist [VP, B] viewed as [NBLK, 128, B]; static one-hot per (dst-slot,
# src-block) is huge; instead simulate cost with random one-hots:
# out = sum_k onehot_k @ dist_blk_k via dot_general batched matmul.
NBLK = VP // 128
SLOTS_PER_BLK = (VP * D) // NBLK  # 8.4M slots spread over 784 blocks ~ 8192


def body_g(c):
    dist, oh = c
    # dist [NBLK, 128, B] ; oh [NBLK, SLOTS, 128] int8 -> batched matmul
    d3 = dist.reshape(NBLK, 128, B)
    lo = (d3 & 0x7FFF).astype(jnp.bfloat16)
    hi = (d3 >> 15).astype(jnp.bfloat16)
    glo = jax.lax.dot_general(
        oh, lo, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    ghi = jax.lax.dot_general(
        oh, hi, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    g = (ghi.astype(jnp.int32) << 15) + glo.astype(jnp.int32)
    new = g.reshape(NBLK, SLOTS_PER_BLK, B).min(axis=1)  # fake reduce
    d_new = jnp.broadcast_to(new[:, None, :], (NBLK, 128, B)).reshape(VP, B)
    return (jnp.minimum(dist, d_new), oh)


oh_h = np.zeros((NBLK, SLOTS_PER_BLK, 128), dtype=np.int8)
oh_h[:, :, 0] = 1
bench("G  onehot bf16 MXU (batched)", body_g,
      (jnp.asarray(dist_h), jnp.asarray(oh_h)), ROWS)


# ---- H: sort-based relax: src-major cand + sort by dst + seg-scan ----
# static src-major edge list: dst ids per (src-major) slot
dst_of_slot_h = rng.integers(0, VP, size=(2 * 1024 * 1024,), dtype=np.int32)
dst_sorted_h = np.sort(dst_of_slot_h)
E2 = dst_of_slot_h.shape[0]


def body_h(c):
    dist, = c
    # cand gen: free (use dist col 0 + const); sort (dst, cand) pairs
    cand = dist[: E2 // B].reshape(-1)[:E2] + 1  # fake, elementwise
    key = jnp.asarray(dst_sorted_h)  # already sorted: best case
    ks, vs = jax.lax.sort([key, cand], num_keys=1)
    # segmented min via associative scan on runs? approximate with sort
    # by (dst, val): min is first of each run; emulate extraction cost:
    first = jnp.concatenate(
        [jnp.ones((1,), bool), ks[1:] != ks[:-1]]
    )
    upd = jnp.where(first, vs, INF)
    new = jax.ops.segment_min(
        upd, ks, num_segments=VP, indices_are_sorted=True
    )
    return (jnp.minimum(dist, new[:, None]),)


bench("H  sort+segmin (E=2.1M, B=1)", body_h, (jnp.asarray(dist_h),), E2)
