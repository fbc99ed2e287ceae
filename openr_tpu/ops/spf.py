"""Shared pieces of the SPF compute core: the numeric contract, the
first-hop / LFA identities, KSP's table layout and the reference solve.

reference: openr/decision/LinkState.cpp † runSpf — a per-root scalar
Dijkstra with a std::priority_queue. A priority queue is the wrong shape for
a TPU: data-dependent control flow, scalar pops, pointer chasing. The
TPU-native formulation is **batched relaxation to fixpoint** (Bellman-Ford
over in-neighbor tables):

    dist[v, b] = min(dist[v, b], min over edges (u→v): dist[u, b] + w(u,v))

iterated until no distance changes, as a row gather + elementwise add +
axis-min: static shapes, no host sync, no scatter, and the batch dimension
B (SPF roots) vectorizes for free. ECMP/LFA/nexthops then fall out of pure
elementwise comparisons on the resulting distance matrix
(`first_hop_matrix`, `lfa_matrix`) instead of predecessor bookkeeping
inside the loop.

The kernels the program solves with are in `ops/spf_split.py` (split-width
tables; its fused RIB program calls `first_hop_matrix` / `lfa_matrix`).
What lives here beside the identities: `build_dense_tables`, the
full-width in-neighbor layout `ops/ksp.py` masks edges in, and
`batched_sssp_dense`, the plain recurrence over that layout which tests
hold the split kernel and the C++ engine to.

Layout notes (TPU):
  * node-major [Vp, B]: B is the minor (lane) dim; pad B to a multiple
    of 8 — callers use `pad_batch`.
  * distances are **int32** (exact integer metrics, like the reference's
    int metrics). INF_DIST = 2^30; valid metrics ≤ METRIC_MAX = 2^30-1
    (clamped by the CSR builder — covers the reference's practical metric
    range), and the relax computes min(dist + metric, INF) guarded by
    dist < INF, so the sum never exceeds INT32_MAX — no overflow. Path
    costs saturate at INF (≥ INF ⇒ unreachable); the oracle saturates
    identically. Padding slots carry metric == INF_DIST exactly.
  * overload (no-transit) is a per-element mask on the source node of an
    edge, lifted in the batch column whose root IS that node (reference:
    SpfSolver † lets an overloaded node source/sink traffic, never
    transit it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.common import constants as _C
from openr_tpu.common.util import pad_bucket as pad_batch  # roots bucket

# Single source of truth for the solver numeric contract lives in
# common/constants.py (shared with the CSR builder and the oracle clamp).
INF_DIST = np.int32(_C.DIST_INF)
METRIC_MAX = np.int32(_C.METRIC_MAX)
DIST_DTYPE = jnp.int32


@jax.jit
def first_hop_matrix(
    dist: jax.Array,  # [Vp, B]: col 0 = root, cols 1..N = its neighbors
    neighbor_metric: jax.Array,  # [N] i32 metric(root → neighbor i)
    neighbor_ids: jax.Array,  # [N] i32 node id of neighbor i
    neighbor_overloaded: jax.Array,  # [N] bool
) -> jax.Array:
    """ECMP first-hop validity: valid[n, d] ⇔ neighbor n is a shortest-path
    first hop from the root toward destination node d.

    The identity: n is a valid first hop for d iff
        metric(root→n) + dist_n(d) == dist_root(d).
    No predecessor bookkeeping needed (the reference instead collects all
    equal-cost parents inside Dijkstra: LinkState.cpp † runSpf); the same
    ECMP DAG is recovered from the distance matrix by elementwise compare —
    and the neighbor-rooted rows double as the LFA backup-path inputs.

    Overloaded neighbors are excluded for every destination except
    themselves (no-transit, destination still reachable).
    """
    d_root = dist[:, 0]  # [Vp]
    d_nbr = dist[:, 1 : 1 + neighbor_ids.shape[0]]  # [Vp, N]
    reach = (d_root < INF_DIST)[:, None] & (d_nbr < INF_DIST)
    on_spt = reach & (neighbor_metric[None, :] + d_nbr == d_root[:, None])
    dest_is_nbr = jnp.arange(dist.shape[0])[:, None] == neighbor_ids[None, :]
    allowed = ~neighbor_overloaded[None, :] | dest_is_nbr
    return (on_spt & allowed).T  # [N, Vp]


@jax.jit
def lfa_matrix(
    dist: jax.Array,  # [Vp, B]: col 0 = root, cols 1..N = its neighbors
    my_id: jax.Array,  # scalar i32: the root's node id
    neighbor_ids: jax.Array,  # [N] i32 node id of neighbor i
    neighbor_overloaded: jax.Array,  # [N] bool
) -> jax.Array:
    """RFC 5286 loop-free alternates: lfa[n, d] ⇔ neighbor n's shortest
    path to destination d provably avoids the root:

        dist_n(d) < dist_n(root) + dist_root(d)

    All three terms are rows/columns of the batched solve's distance
    matrix, so LFA costs one elementwise compare — no extra SPF runs
    (the reference's legacy LFA re-ran Dijkstra per neighbor †).
    dist_n(root) is read at the root's row of the neighbor's own column
    (direction-correct under asymmetric metrics). Overloaded neighbors
    are excluded except when they ARE the destination; the guard against
    n_to_root being INF (partitioned neighbor) is the reach mask plus
    int32 saturation in the comparison.
    """
    d_root = dist[:, 0]  # [Vp] dist(root → d)
    d_nbr = dist[:, 1 : 1 + neighbor_ids.shape[0]]  # [Vp, N] dist(n → d)
    n_to_root = dist[my_id, 1 : 1 + neighbor_ids.shape[0]]  # [N] dist(n → root)
    reach = (
        (d_root < INF_DIST)[:, None]
        & (d_nbr < INF_DIST)
        & (n_to_root < INF_DIST)[None, :]
    )
    loop_free = d_nbr < jnp.minimum(
        n_to_root[None, :] + d_root[:, None], INF_DIST
    )
    dest_is_nbr = jnp.arange(dist.shape[0])[:, None] == neighbor_ids[None, :]
    allowed = ~neighbor_overloaded[None, :] | dest_is_nbr
    return (reach & loop_free & allowed).T  # [N, Vp]


def build_dense_tables(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_metric: np.ndarray,
    num_nodes_padded: int,
    min_width: int = 8,
) -> tuple[np.ndarray, np.ndarray]:
    """Dense in-neighbor tables: nbr[Vp, D] i32, wgt[Vp, D] i32 (INF pad).

    TPU rationale: `segment_min` lowers to a scatter-min, which serializes
    on TPU (~45 ms per relax over 2M edges measured on v5e). Rewriting the
    relax as   dist_new[v] = min_d dist[nbr[v, d]] + wgt[v, d]   turns it
    into a row gather + axis-min — no scatter at all — and measured ~2-4x
    faster end-to-end, with the further upside that gather cost scales with
    *rows gathered*, so degree-aware packing can shrink it again.

    Requires edge arrays sorted by dst (the CsrGraph layout). D is the
    next power of two ≥ max in-degree.
    """
    valid = edge_metric < int(INF_DIST)
    src = edge_src[valid].astype(np.int64)
    dst = edge_dst[valid].astype(np.int64)
    met = edge_metric[valid]
    e = src.shape[0]
    indeg = np.bincount(dst, minlength=num_nodes_padded)
    max_deg = int(indeg.max()) if e else 1
    d_width = pad_batch(max_deg, minimum=min_width)  # shared pad_bucket
    nbr = np.zeros((num_nodes_padded, d_width), dtype=np.int32)
    wgt = np.full((num_nodes_padded, d_width), INF_DIST, dtype=np.int32)
    if e:
        # column slot for edge i = i - first_index_of(dst[i]) (dst-sorted)
        row_start = np.zeros(num_nodes_padded + 1, dtype=np.int64)
        np.add.at(row_start, dst + 1, 1)
        row_start = np.cumsum(row_start)
        col = np.arange(e, dtype=np.int64) - row_start[dst]
        nbr[dst, col] = src.astype(np.int32)
        wgt[dst, col] = met
    return nbr, wgt


@functools.partial(jax.jit, static_argnames=("has_overloads",))
def batched_sssp_dense(
    nbr: jax.Array,  # [Vp, D] i32 in-neighbor ids (0 + INF wgt for padding)
    wgt: jax.Array,  # [Vp, D] i32 metric; INF_DIST padding
    node_overloaded: jax.Array,  # [Vp] bool
    roots: jax.Array,  # [B] i32
    has_overloads: bool = True,
) -> jax.Array:
    """The plain Bellman-Ford over the full-width tables → dist [Vp, B]
    int32 (see build_dense_tables): what the split kernel's and the
    native engine's tests compare against at sizes where the Python
    oracle is too slow, and the recurrence `ops/ksp.py` repeats under
    its edge bans. No caller in the program.

    The overloaded-transit rule is a fused per-element mask — an edge
    from an overloaded node relaxes only in the batch column whose root IS
    that node (`has_overloads=False` drops the mask entirely: the common
    case).
    """
    num_nodes = nbr.shape[0]
    b = roots.shape[0]
    dist = jnp.full((num_nodes, b), INF_DIST, DIST_DTYPE)
    dist = dist.at[roots, jnp.arange(b)].set(0)

    if has_overloads:
        over_t = node_overloaded[nbr]  # [Vp, D] src-overloaded

    def relax(state):
        dist, _changed, it = state
        d = dist[nbr]  # [Vp, D, B] row gather
        cand = jnp.where(
            d < INF_DIST, jnp.minimum(d + wgt[:, :, None], INF_DIST), INF_DIST
        )
        if has_overloads:
            blocked = over_t[:, :, None] & (
                nbr[:, :, None] != roots[None, None, :]
            )
            cand = jnp.where(blocked, INF_DIST, cand)
        new = jnp.minimum(cand.min(axis=1), dist)
        return new, jnp.any(new < dist), it + 1

    def cond(state):
        _dist, changed, it = state
        return changed & (it < num_nodes)

    dist, _, _ = jax.lax.while_loop(cond, relax, (dist, jnp.bool_(True), 0))
    return dist
