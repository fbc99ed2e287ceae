"""Cost model probes for the SPF kernel redesign (v5e, real chip).

To take the per-dispatch cost out of the reading, every probe
runs K in-jit iterations (lax.fori_loop with a data dependency between
iterations to defeat CSE/DCE) and reports (tK - t1) / (K - 1).
Arrays are freed between probes to stay inside HBM.
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
K = 16


def _leaf(out):
    leaves = jax.tree_util.tree_leaves(out)
    return float(jnp.asarray(leaves[0]).reshape(-1)[0])


def timed(fn, *args, n=5):
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


def bench(name, make_body, init, unit_count, unit="rows"):
    try:
        @functools.partial(jax.jit, static_argnames=("k",))
        def run(init, k):
            return jax.lax.fori_loop(0, k, lambda i, c: make_body(c), init)

        t1 = timed(lambda a: run(a, 1), init)
        tk = timed(lambda a: run(a, K), init)
        per = (tk - t1) / (K - 1)
        if per <= 0.005:
            print(f"  {name:46s} per-iter <0.01 ms (t1={t1:.1f} tK={tk:.1f})")
            return
        rate = unit_count / (per / 1e3) / 1e9
        print(f"  {name:46s} per-iter {per:8.2f} ms   {rate:7.3f} G{unit}/s")
    except Exception as e:  # noqa: BLE001
        lines = [l for l in str(e).splitlines() if l.strip()] or [repr(e)]
        print(f"  {name:46s} FAIL {lines[0][:120]}")
    finally:
        gc.collect()


print(f"# device: {jax.devices()[0]}  (K={K} in-jit iters, dispatch-corrected)")

VP = 131072
D = 64


def probe_gather_width(bw, m):
    tbl = jnp.asarray(rng.integers(0, 1 << 20, size=(VP, bw), dtype=np.int32))
    idx0 = jnp.asarray(rng.integers(0, VP, size=(m,), dtype=np.int32))
    acc0 = jnp.full((m, bw), np.int32(1 << 30), jnp.int32)

    def body(c):
        idx, acc = c
        g = tbl[idx]
        acc = jnp.minimum(acc, g)
        idx = (idx + acc[:, 0]) & (VP - 1)
        return (idx, acc)

    bench(f"gather [{VP}x{bw}] x {m/1e6:.1f}M rows", body, (idx0, acc0), m)


probe_gather_width(1, 1 << 23)
probe_gather_width(8, 1 << 23)
probe_gather_width(32, 1 << 22)
probe_gather_width(128, 1 << 20)


def probe_gather_rows(m):
    tbl = jnp.asarray(rng.integers(0, 1 << 20, size=(VP, 32), dtype=np.int32))
    idx0 = jnp.asarray(rng.integers(0, VP, size=(m,), dtype=np.int32))
    acc0 = jnp.full((m, 32), np.int32(1 << 30), jnp.int32)

    def body(c):
        idx, acc = c
        g = tbl[idx]
        acc = jnp.minimum(acc, g)
        idx = (idx + acc[:, 0]) & (VP - 1)
        return (idx, acc)

    bench(f"gather [{VP}x32] x {m/1e6:.2f}M rows", body, (idx0, acc0), m)


probe_gather_rows(1 << 18)
probe_gather_rows(1 << 20)


def probe_small_table():
    small = 1 << 14
    m = 1 << 20
    tbl = jnp.asarray(
        rng.integers(0, 1 << 20, size=(small, 32), dtype=np.int32)
    )
    idx0 = jnp.asarray(rng.integers(0, small, size=(m,), dtype=np.int32))
    acc0 = jnp.full((m, 32), np.int32(1 << 30), jnp.int32)

    def body(c):
        idx, acc = c
        g = tbl[idx]
        acc = jnp.minimum(acc, g)
        idx = (idx + acc[:, 0]) & (small - 1)
        return (idx, acc)

    bench(f"gather [{small}x32] x 1.0M rows", body, (idx0, acc0), m)


probe_small_table()


def probe_taa():
    dist0 = jnp.asarray(
        rng.integers(0, 1 << 20, size=(VP, 32), dtype=np.int32)
    )
    ptr0 = jnp.asarray(rng.integers(0, VP, size=(VP, 32), dtype=np.int32))

    def body(c):
        ptr, d = c
        g = jnp.take_along_axis(d, ptr, axis=0)
        d = jnp.minimum(d, g)
        ptr = (ptr + d) & (VP - 1)
        return (ptr, d)

    bench(f"take_along_axis [{VP}x32] 4.2M elem", body, (ptr0, dist0),
          VP * 32, unit="elems")


probe_taa()


def probe_seg(sorted_, width):
    E = 2 * 1024 * 1024
    if width == 1:
        vals0 = jnp.asarray(
            rng.integers(0, 1 << 20, size=(E,), dtype=np.int32)
        )
        accv = jnp.full((VP,), np.int32(1 << 30), jnp.int32)
    else:
        vals0 = jnp.asarray(
            rng.integers(0, 1 << 20, size=(E, width), dtype=np.int32)
        )
        accv = jnp.full((VP, width), np.int32(1 << 30), jnp.int32)
    ids = rng.integers(0, VP, size=(E,), dtype=np.int32)
    if sorted_:
        ids = np.sort(ids)
    seg = jnp.asarray(ids)

    def body(c):
        vals, acc = c
        r = jax.ops.segment_min(
            vals, seg, num_segments=VP, indices_are_sorted=sorted_
        )
        acc = jnp.minimum(acc, r)
        if width == 1:
            vals = vals + acc[0]
        else:
            vals = vals + acc[:1, :]
        return (vals, acc)

    tag = "sorted" if sorted_ else "unsort"
    bench(f"segment_min {tag} [2.1M x {width}]", body, (vals0, accv),
          E)


probe_seg(True, 32)
probe_seg(False, 32)
probe_seg(False, 1)
probe_seg(True, 1)


def probe_sort(m, kv):
    keys0 = jnp.asarray(rng.integers(0, 1 << 30, size=(m,), dtype=np.int32))
    if kv:
        pay0 = jnp.asarray(
            rng.integers(0, 1 << 30, size=(m,), dtype=np.int32)
        )

        def body(c):
            k, p, acc = c
            ks, ps = jax.lax.sort([k, p], num_keys=1)
            acc = jnp.minimum(acc, ks[0] + ps[0])
            return (k ^ acc, p, acc)

        bench(f"sort_kv {m/1e6:.1f}M i32", body,
              (keys0, pay0, jnp.int32(1 << 30)), m, unit="keys")
    else:
        def body(c):
            k, acc = c
            s = jnp.sort(k)
            acc = jnp.minimum(acc, s[0])
            return (k ^ acc, acc)

        bench(f"sort {m/1e6:.1f}M i32", body, (keys0, jnp.int32(1 << 30)),
              m, unit="keys")


probe_sort(1 << 20, False)
probe_sort(1 << 23, False)
probe_sort(1 << 21, True)


def probe_ew():
    a0 = jnp.asarray(rng.integers(0, 1 << 20, size=(VP, D), dtype=np.int32))
    b0 = jnp.asarray(rng.integers(0, 1 << 20, size=(VP, D), dtype=np.int32))

    def body(c):
        a, b = c
        return (jnp.minimum(a + 1, b), a)

    bench(f"elementwise min+add [{VP}x{D}] 8.4M", body, (a0, b0), VP * D,
          unit="elems")


probe_ew()
