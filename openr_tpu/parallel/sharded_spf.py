"""Sharded batched SSSP: sources × graph partitioning under shard_map.

The single-device kernel (`ops/spf_split.py`) already vectorizes over SPF
roots; here the same relax-to-fixpoint runs SPMD:

  * roots sharded over the ``sources`` mesh axis — each device solves its
    slice of roots independently (no communication);
  * the base in-neighbor table rows sharded over the ``graph`` mesh axis —
    each device relaxes its row slice and the full distance matrix is
    re-assembled with a tiled ICI ``all_gather`` every sweep (the frontier
    exchange; the moral equivalent of the reference's KvStore flood is
    host-side — this is purely the compute-plane collective).

Distances stay replicated across the ``graph`` axis (Vp·B int32 — the
tables dominate HBM, which is exactly what the graph axis shards), so the
fixpoint condition is computed identically on every shard: no extra
convergence collective needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from openr_tpu.ops.spf import INF_DIST
from openr_tpu.parallel.mesh import GRAPH_AXIS, SOURCES_AXIS


def _local_split_sssp(
    base_nbr, base_wgt, ov_ids, ov_nbr, ov_wgt, node_overloaded, roots,
    vp, has_overloads,
):
    """Per-device body for the split-table kernel: this shard owns a
    contiguous row slice of the base in-neighbor tables and relaxes only
    those rows each sweep; the full distance matrix is re-assembled with
    a tiled all_gather over the graph axis (the ICI frontier exchange —
    rows replace pmin because the row partition is disjoint). The tiny
    overflow tables are replicated and relaxed identically everywhere."""
    b = roots.shape[0]
    dist = jnp.full((vp, b), INF_DIST, jnp.int32)
    dist = dist.at[roots, jnp.arange(b)].set(0)
    # the loop carry passes through an all_gather over the graph axis,
    # whose output is varying-on-graph under check_vma; the initial
    # carry must carry the same manual-axes type. (Values stay
    # replicated in fact — every shard computes identical full dist —
    # so per-shard while_loop trip counts coincide and the in-loop
    # collectives stay aligned.)
    dist = jax.lax.pcast(dist, GRAPH_AXIS, to="varying")

    if has_overloads:
        over_rows = node_overloaded[base_nbr]  # [vp/G, W] src-overloaded
        over_ov = node_overloaded[ov_nbr]

    def relax(nbr, wgt, over_t, dist):
        # same measured-fastest formulation as the single-device kernel
        # (d-loop of [R]-row gathers, ops/spf_split._relax_rows)
        from openr_tpu.ops.spf_split import _relax_rows

        return _relax_rows(dist, nbr, wgt, over_t, roots, has_overloads)

    def sweep(state):
        dist, _changed, it = state
        mine = relax(
            base_nbr, base_wgt, over_rows if has_overloads else None, dist
        )
        full = jax.lax.all_gather(
            mine, GRAPH_AXIS, axis=0, tiled=True
        )  # [vp, B]
        new = jnp.minimum(full, dist)
        ov_new = relax(ov_nbr, ov_wgt, over_ov if has_overloads else None, dist)
        new = new.at[ov_ids].min(ov_new)
        return new, jnp.any(new < dist), it + 1

    def cond(state):
        _dist, changed, it = state
        return changed & (it < vp)

    # initial `changed` must carry the same varying-manual-axes type as
    # the loop output (jnp.any over the sources-sharded dist): a literal
    # True is unvarying and check_vma rightly rejects it
    changed0 = jnp.any(dist <= INF_DIST)  # always True, correctly varying
    dist, _, _ = jax.lax.while_loop(cond, sweep, (dist, changed0, 0))
    # dist is replicated in value but varying in type; one identity
    # pmin proves the replication to check_vma for the P(None, sources)
    # out_spec
    return jax.lax.pmin(dist, GRAPH_AXIS)


@functools.partial(
    jax.jit, static_argnames=("mesh", "has_overloads")
)
def sharded_sssp_split(
    base_nbr: jax.Array,   # [vp, W] — vp must divide by the graph axis
    base_wgt: jax.Array,
    ov_ids: jax.Array,     # [Go] (replicated)
    ov_nbr: jax.Array,     # [Go, Wo]
    ov_wgt: jax.Array,
    node_overloaded: jax.Array,  # [vp] bool (replicated)
    roots: jax.Array,      # [B] — B must divide by the sources axis
    mesh: Mesh,
    has_overloads: bool = False,
) -> jax.Array:
    """The flagship v3 split-width kernel (ops/spf_split.py), SPMD over a
    ``sources × graph`` mesh: roots shard over ``sources`` (independent
    solves), the base in-neighbor table rows shard over ``graph`` (HBM
    scaling — the tables dominate at 100k nodes), with one tiled
    all_gather per sweep over ICI. Distances equal the single-device
    kernel's (tests/test_parallel.py)."""
    vp = base_nbr.shape[0]
    g = mesh.shape[GRAPH_AXIS]
    if vp % g:
        raise ValueError(f"vp={vp} must divide by graph axis size {g}")
    fn = jax.shard_map(
        functools.partial(
            _local_split_sssp, vp=vp, has_overloads=has_overloads
        ),
        mesh=mesh,
        in_specs=(
            P(GRAPH_AXIS, None),
            P(GRAPH_AXIS, None),
            P(None),
            P(None, None),
            P(None, None),
            P(None),
            P(SOURCES_AXIS),
        ),
        out_specs=P(None, SOURCES_AXIS),
        check_vma=True,
    )
    return fn(
        base_nbr, base_wgt, ov_ids, ov_nbr, ov_wgt, node_overloaded, roots
    )
