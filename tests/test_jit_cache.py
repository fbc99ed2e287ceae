"""jit-cache stability of the device kernels under equivalent-but-
distinct inputs.

The jit cache keys on dtype, weak-type AND commitment — a python int,
an ``np.int32`` scalar and a ``jnp.int32`` array are three cache
entries for identical math (measured on jax 0.4.37). The ops layer's
canonicalizing entry point (``ops/ksp.py``) exists so every
equivalent call spelling lands on ONE compiled variant,
and the padding buckets make every batch size inside a bucket share a
shape. These tests pin both, two ways: exact ``_cache_size`` deltas on
the kernels, and the conftest compile sanitizer
(``@pytest.mark.jit_steady_state`` + ``compile_ledger.mark_warm()``)
failing the test on ANY steady-state compilation.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.monitor import compile_ledger
from openr_tpu.ops.ksp import (
    _ksp_edge_disjoint_dense_jit,
    build_ksp_blocked,
    ksp_edge_disjoint_dense,
)
from openr_tpu.ops.spf import build_dense_tables, pad_batch


def _line_graph(n: int):
    """0-1-2-...-(n-1) line, metric 1 both ways, dense tables."""
    edges = []
    for i in range(n - 1):
        edges.append((i, i + 1, 1))
        edges.append((i + 1, i, 1))
    edges.sort(key=lambda e: (e[1], e[0]))
    src = np.array([e[0] for e in edges], np.int32)
    dst = np.array([e[1] for e in edges], np.int32)
    met = np.array([e[2] for e in edges], np.int32)
    return build_dense_tables(src, dst, met, n)


def _pad(dests, root_id: int) -> np.ndarray:
    out = np.full(pad_batch(len(dests)), root_id, np.int32)
    out[: len(dests)] = dests
    return out


@pytest.mark.jit_steady_state
def test_ksp_cache_stable_across_equivalent_spellings():
    n = 12
    nbr, wgt = _line_graph(n)
    blocked = build_ksp_blocked(nbr, np.zeros(n, bool), 0)
    kw = dict(k=2, max_hops=n - 1)

    # every equivalent spelling of the same call must share ONE kernel
    # variant: python-int root, np scalar, jnp scalar; np tables vs jnp
    # tables; list-built dests in the same pad bucket
    spellings = [
        dict(),
        dict(root=np.int32(0)),
        dict(root=jnp.int32(0)),
        dict(nbr=jnp.asarray(nbr), wgt=jnp.asarray(wgt)),
        dict(dests=_pad([7, 9], 0)),          # same bucket, new values
        dict(dests=_pad([1, 2, 3], 0)),       # same bucket, new raw size
    ]

    def run_all():
        out = None
        for sp in spellings:
            args = dict(
                nbr=nbr, wgt=wgt, blocked=blocked, root=0,
                dests=_pad([3, 5], 0),
            )
            args.update(sp)
            out = ksp_edge_disjoint_dense(
                args["nbr"], args["wgt"], args["blocked"], args["root"],
                args["dests"], **kw,
            )
        return out

    # warmup pass: ONE kernel compile covers every spelling (the tiny
    # eager canonicalization ops warm per input type here too)
    run_all()
    size_after_warm = ksp_edge_disjoint_dense.cache_size()
    compile_ledger.mark_warm()
    # steady-state pass: all spellings again — zero compiles anywhere
    # (kernel asserted here; eager ops by the jit_steady_state fixture)
    base = run_all()
    assert ksp_edge_disjoint_dense.cache_size() == size_after_warm, (
        "equivalent-but-distinct inputs minted new jit cache entries"
    )
    # sanity: the warm variant still computes (line graph: d(0->1)=1)
    assert int(np.asarray(base[0])[0, 0]) == 1


def test_ksp_uncanonicalized_scalars_would_split_the_cache():
    """The negative control: calling the raw jitted kernel with a
    python int vs an np.int32 root really does mint two cache entries
    — the hazard the canonicalizing wrapper (and orlint OR008-OR010's
    weak-type rules) exists for. If a jax upgrade ever unifies the
    keys, this test flags the wrapper as droppable."""
    n = 8
    nbr, wgt = _line_graph(n)
    blocked = jnp.asarray(build_ksp_blocked(nbr, np.zeros(n, bool), 0))
    nbr_d, wgt_d = jnp.asarray(nbr), jnp.asarray(wgt)
    dests = jnp.asarray(_pad([2], 0))
    size0 = _ksp_edge_disjoint_dense_jit._cache_size()
    _ksp_edge_disjoint_dense_jit(
        nbr_d, wgt_d, blocked, 0, dests, k=2, max_hops=n - 1
    )
    _ksp_edge_disjoint_dense_jit(
        nbr_d, wgt_d, blocked, np.int32(0), dests, k=2, max_hops=n - 1
    )
    assert _ksp_edge_disjoint_dense_jit._cache_size() - size0 == 2


@pytest.mark.jit_steady_state
def test_split_rib_cache_stable_same_bucket_different_batch():
    """Same pad bucket, different real neighbor count: the production
    RIB solve discipline (spf_backend._rib_pad_arrays) keeps one
    compiled batched_sssp_split_rib variant — churn that adds or drops
    an adjacency inside the bucket must be a cache hit."""
    from openr_tpu.ops.spf_split import (
        batched_sssp_split_rib,
        build_split_tables,
        tight_nodes,
    )

    n = 20
    edges = []
    for i in range(n - 1):
        edges.append((i, i + 1, 1))
        edges.append((i + 1, i, 1))
    edges.sort(key=lambda e: (e[1], e[0]))
    t = build_split_tables(
        np.array([e[0] for e in edges], np.int32),
        np.array([e[1] for e in edges], np.int32),
        np.array([e[2] for e in edges], np.int32),
        n,
    )
    vp = t["vp"]
    assert vp == tight_nodes(n)
    dead = vp - 1
    over = np.zeros(vp, bool)

    def solve(nbr_ids):
        b = pad_batch(1 + len(nbr_ids))
        roots = np.full(b, 0, np.int32)
        roots[1 : 1 + len(nbr_ids)] = nbr_ids
        ids = np.full(b - 1, dead, np.int32)
        ids[: len(nbr_ids)] = nbr_ids
        metric = np.full(b - 1, 1, np.int32)
        nbr_over = np.ones(b - 1, bool)
        nbr_over[: len(nbr_ids)] = False
        return batched_sssp_split_rib(
            jnp.asarray(t["base_nbr"]), jnp.asarray(t["base_wgt"]),
            jnp.asarray(t["ov_ids"]), jnp.asarray(t["ov_nbr"]),
            jnp.asarray(t["ov_wgt"]), jnp.asarray(t["out_nbr"]),
            jnp.asarray(over), jnp.asarray(roots),
            jnp.asarray(metric), jnp.asarray(ids),
            jnp.asarray(nbr_over), jnp.int32(0),
        )

    solve([1])  # warm the b=8 bucket variant
    size0 = batched_sssp_split_rib._cache_size()
    compile_ledger.mark_warm()
    solve([1, 2])   # 2 neighbors: same bucket
    solve([1, 2, 3])
    assert batched_sssp_split_rib._cache_size() == size0
