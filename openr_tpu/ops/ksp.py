"""Vectorized k edge-disjoint shortest paths (KSP) on device.

reference: openr/decision/SpfSolver.cpp † selectBestPathsKsp2 computes TWO
edge-disjoint paths per SR prefix by running scalar Dijkstra, pruning the
first path's links, and running Dijkstra again — per prefix, on the host.
This module is the TPU-native generalization to k ≤ 16 (BASELINE config
4): one call computes k edge-disjoint paths for a whole BATCH of
(root → dest) jobs at once.

Design (all shapes static, no host round-trips inside):

  * graph is the dense in-neighbor table of ops/spf.py
    (``build_dense_tables``): nbr/wgt [Vp, D].
  * per-job edge bans are DATA, not shape: ``banned`` [Vp, D, B] bool —
    the masked re-solve trick from the reference, vectorized over jobs.
  * each of the k rounds is (a) a batched masked SSSP relaxation to
    fixpoint (same recurrence as ``batched_sssp_dense``), then (b) a
    batched back-walk extracting one shortest path per job under the
    deterministic predecessor rule shared with the CPU oracle
    (``decision/ksp.py extract_path``): at node v pick the
    smallest-node-id predecessor p with dist[p] + w(p,v) == dist[v].
    Node ids are interned in sorted-name order (LinkState.to_csr), so
    smallest-id == lexicographically-smallest-name — device paths are
    byte-identical to oracle paths.
  * the walked path's links are banned in BOTH directions (all parallel
    slots between the node pair) before the next round, matching the
    oracle's ``path_links``.

The k rounds run under ``lax.scan`` — k is static, banned is the carry.
Distances strictly decrease along a back-walk (metrics ≥ 1), so the walk
needs no visited-set and terminates in ≤ max_hops steps.

The phases carry ``jax.named_scope`` names, as ops/spf_split.py's do, so
a device trace of the kernel reads by phase: ``first_solve`` (round 1's
shared, ban-free distances), ``round/ban_mask`` + ``round/fixpoint`` (a
later round's masked SSSP), ``round/walk`` (the back-walk and its bans),
``round/emit`` (the round's rows of the outputs). Metadata only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.ops.spf import DIST_DTYPE, INF_DIST
from openr_tpu.ops.spf_split import _UNROLL_MAX_W


def build_ksp_blocked(
    nbr: np.ndarray, node_overloaded: np.ndarray, root_id: int
) -> np.ndarray:
    """Host-side base mask [Vp, D]: slots whose source node may not carry
    transit traffic — every in-edge from an overloaded node, except the
    root's own out-edges (an overloaded root still sources traffic;
    reference: SpfSolver overload semantics †)."""
    return node_overloaded[nbr] & (nbr != root_id)


@functools.partial(jax.jit, static_argnames=("k", "max_hops"))
def _ksp_edge_disjoint_dense_jit(
    nbr: jax.Array,  # [Vp, D] i32 in-neighbor ids (padding: wgt == INF)
    wgt: jax.Array,  # [Vp, D] i32 metric; INF_DIST padding
    blocked: jax.Array,  # [Vp, D] bool base mask (build_ksp_blocked)
    root: jax.Array,  # scalar i32 — shared SPF root (this node)
    dests: jax.Array,  # [B] i32 destination node per job
    *,
    k: int,
    max_hops: int,
    dist0: jax.Array | None = None,  # [Vp] i32, see below
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (costs [k, B] i32, paths [k, B, max_hops+1] i32, hops [k, B]).

    ``paths[i, b]`` is the i-th edge-disjoint shortest path for job b in
    WALK order (dest first, root last), -1 padded; ``costs[i, b]`` is
    INF_DIST when no i-th disjoint path exists. Rounds are emitted in
    computation order; successive costs are non-decreasing.

    ``dist0`` (optional; None vs array is structurally static under
    jit): precomputed UNBANNED distances from ``root`` under the same
    blocked/overload semantics — round 1 has no bans, so its SSSP
    result is identical for every job and the caller usually has it
    already (the production solve's d_root).
    Skipping the round-1 fixpoint saves 1/k_eff of the solve cost —
    material on high-diameter graphs where each fixpoint runs
    ~diameter sweeps (round-4 verdict item 5, "share the k=1 solve").
    """
    num_nodes, _d = nbr.shape
    b = dests.shape[0]
    bidx = jnp.arange(b)

    def sssp(banned):
        with jax.named_scope("round/ban_mask"):
            dist = jnp.full((num_nodes, b), INF_DIST, DIST_DTYPE)
            dist = dist.at[root, :].set(0)
            usable = (~blocked[:, :, None]) & (~banned) & (
                wgt[:, :, None] < INF_DIST
            )
        width = nbr.shape[1]

        def relax(state):
            dist, _changed, it = state
            if width <= _UNROLL_MAX_W:  # shared bound with spf_split
                # d-loop of [Vp]-row gathers — the measured-fastest
                # gather form on v5e (0.609 G rows/s vs 0.26-0.35 for
                # the single [Vp, D]-index gather; probe_gather_forms,
                # docs/spf_kernel_profile.md §2), ported from the
                # headline split kernel. Same fixpoint, same guarded
                # select (the 2-op algebraic form measured SLOWER on
                # chip — see the 2026-07-31 negative result).
                acc = jnp.full_like(dist, INF_DIST)
                for col in range(width):
                    g = dist[nbr[:, col]]  # [Vp, B] row gather
                    c = jnp.where(
                        usable[:, col, :] & (g < INF_DIST),
                        jnp.minimum(
                            g + wgt[:, col][:, None], INF_DIST
                        ),
                        INF_DIST,
                    )
                    acc = jnp.minimum(acc, c)
                new = jnp.minimum(acc, dist)
                return new, jnp.any(new < dist), it + 1
            d = dist[nbr]  # [Vp, D, B]
            cand = jnp.where(
                usable & (d < INF_DIST),
                jnp.minimum(d + wgt[:, :, None], INF_DIST),
                INF_DIST,
            )
            new = jnp.minimum(cand.min(axis=1), dist)
            return new, jnp.any(new < dist), it + 1

        def cond(state):
            _dist, changed, it = state
            return changed & (it < num_nodes)

        with jax.named_scope("round/fixpoint"):
            dist, _, _ = jax.lax.while_loop(
                cond, relax, (dist, jnp.bool_(True), 0)
            )
        return dist

    def walk(dist, banned):
        """Trace one path per job and ban its links both ways."""
        cost = dist[dests, bidx]  # [B]
        start_ok = (cost < INF_DIST) & (dests != root)
        cur = jnp.where(start_ok, dests, root)
        path = jnp.full((b, max_hops + 1), -1, jnp.int32)
        path = path.at[:, 0].set(jnp.where(start_ok, dests, -1))

        def step(state):
            cur, path, banned, h, alive, failed = state
            rows_n = nbr[cur]  # [B, D]
            rows_w = wgt[cur]  # [B, D]
            d_cur = dist[cur, bidx]  # [B]
            d_pre = dist[rows_n, bidx[:, None]]  # [B, D]
            row_block = blocked[cur] | banned[cur, :, bidx]
            valid = (
                (~row_block)
                & (rows_w < INF_DIST)
                & (d_pre < INF_DIST)
                & (d_pre + rows_w == d_cur[:, None])
                & alive[:, None]
            )
            # smallest node id among valid predecessors — the shared
            # deterministic rule (ids are interned in sorted-name order)
            pred = jnp.where(valid, rows_n, num_nodes).min(axis=1)
            found = (pred < num_nodes) & alive
            failed = failed | (alive & ~found)
            pred = jnp.where(found, pred, cur)
            # ban pred→cur (row cur, slots nbr==pred) and cur→pred (row
            # pred, slots nbr==cur): every parallel slot, both directions
            f_row = banned[cur, :, bidx]
            f_row = f_row | ((rows_n == pred[:, None]) & found[:, None])
            banned = banned.at[cur, :, bidx].set(f_row)
            r_row = banned[pred, :, bidx]
            r_row = r_row | ((nbr[pred] == cur[:, None]) & found[:, None])
            banned = banned.at[pred, :, bidx].set(r_row)
            path = path.at[:, h + 1].set(jnp.where(found, pred, -1))
            cur = jnp.where(found, pred, cur)
            alive = found & (pred != root)
            return cur, path, banned, h + 1, alive, failed

        def cond(state):
            _cur, _path, _banned, h, alive, _failed = state
            return jnp.any(alive) & (h < max_hops)

        state = (
            cur,
            path,
            banned,
            jnp.int32(0),
            start_ok,
            jnp.zeros_like(start_ok),
        )
        cur, path, banned, h, alive, failed = jax.lax.while_loop(
            cond, step, state
        )
        failed = failed | alive  # ran out of max_hops mid-walk
        ok = start_ok & ~failed
        cost = jnp.where(ok, cost, INF_DIST)
        hops = (path >= 0).sum(axis=1) - 1
        hops = jnp.where(ok, hops, 0)
        return cost, path, hops, banned, ok

    # k rounds with EARLY EXIT (round-4 verdict item 5): bans only ever
    # grow, so a round in which NO job finds a path leaves `banned`
    # unchanged and every later round is doomed to the identical
    # failure — stop dispatching SSSP fixpoints the moment a round
    # comes back empty. In the config-4 backbone (node degree 2-4,
    # k=16) this skips most of the rounds even without the host-side
    # k clamp in _ksp_batch. Outputs for skipped rounds keep the same
    # encoding as failed rounds (cost INF, path -1, hops 0), which is
    # exactly what the oracle's per-prefix `break` produces.
    costs0 = jnp.full((k, b), INF_DIST, DIST_DTYPE)
    paths0 = jnp.full((k, b, max_hops + 1), -1, jnp.int32)
    hops0 = jnp.zeros((k, b), jnp.int32)
    banned0 = jnp.zeros((num_nodes, nbr.shape[1], b), bool)

    def round_cond(state):
        _banned, _c, _p, _h, i, live = state
        return live & (i < k)

    def round_body(state):
        banned, costs, paths, hops, i, _live = state
        if dist0 is not None:
            # round 1 is ban-free and shared: broadcast the caller's
            # precomputed distances instead of running the fixpoint
            def first_solve():
                with jax.named_scope("first_solve"):
                    return jnp.broadcast_to(
                        dist0[:, None], (num_nodes, b)
                    ).astype(DIST_DTYPE)

            dist = jax.lax.cond(i == 0, first_solve, lambda: sssp(banned))
        else:
            dist = sssp(banned)
        with jax.named_scope("round/walk"):
            cost, path, hop, banned, ok = walk(dist, banned)
        with jax.named_scope("round/emit"):
            path = jnp.where(ok[:, None], path, -1)
            costs = costs.at[i].set(cost)
            paths = paths.at[i].set(path)
            hops = hops.at[i].set(hop)
        return banned, costs, paths, hops, i + 1, jnp.any(ok)

    _, costs, paths, hops, _, _ = jax.lax.while_loop(
        round_cond,
        round_body,
        (banned0, costs0, paths0, hops0, jnp.int32(0), jnp.bool_(True)),
    )
    return costs, paths, hops


def ksp_edge_disjoint_dense(
    nbr,
    wgt,
    blocked,
    root,
    dests,
    *,
    k: int,
    max_hops: int,
    dist0=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Canonicalizing entry point for the jitted kernel above.

    The jit cache keys on dtype AND weak-type/commitment, so a python
    int root, an ``np.int32`` scalar, and a ``jnp.int32`` array are
    three distinct cache entries for identical math — measured three
    compiles on jax 0.4.37 (tests/test_jit_cache.py pins this). Every
    array is coerced to its strong contract dtype here, once, so all
    equivalent call spellings share one compiled variant.
    """
    from openr_tpu.monitor import device as device_telemetry

    args = (
        jnp.asarray(nbr, jnp.int32),
        jnp.asarray(wgt, jnp.int32),
        jnp.asarray(blocked, bool),
        jnp.asarray(root, jnp.int32),
        jnp.asarray(dests, jnp.int32),
    )
    kwargs = {
        "k": k,
        "max_hops": max_hops,
        "dist0": None if dist0 is None else jnp.asarray(dist0, DIST_DTYPE),
    }
    # kernel cost ledger (docs/Monitor.md "Device telemetry"): lowers +
    # AOT-compiles only when the compile ledger counted a fresh variant
    # of this fn; the call below then reuses that executable (jit cache
    # is shared with the AOT path — pinned by the telemetry smoke).
    # Runs BEFORE the dispatch so the wrapper keeps its direct-return
    # jit-delegation shape (the orlint jit registry follows it).
    device_telemetry.observe(
        "_ksp_edge_disjoint_dense_jit",
        lambda: _ksp_edge_disjoint_dense_jit.lower(*args, **kwargs),
        span="spf:ksp",
    )
    return _ksp_edge_disjoint_dense_jit(*args, **kwargs)


# the undecorated kernel body, for tests that re-jit it under forced
# configs (test_ksp_relax_branches_agree), and the compiled-variant
# count for the jit-cache stability suite
ksp_edge_disjoint_dense.__wrapped__ = (
    _ksp_edge_disjoint_dense_jit.__wrapped__
)
ksp_edge_disjoint_dense.cache_size = (
    _ksp_edge_disjoint_dense_jit._cache_size
)


def paths_to_host(
    costs,  # [k, B] ints: the fetched array or its .tolist()
    paths: np.ndarray,  # [k, B, L] walk order (dest..root), -1 padded
    hops,  # [k, B] ints, like costs: the kernel's third output
    node_names: list[str],
    job: int,
) -> list[tuple[int, list[str]]]:
    """Device output → the oracle's [(cost, [root..dest names]), ...]
    sorted by (cost, path) exactly like k_edge_disjoint_paths.

    Reads the ``hops + 1`` nodes the kernel found and none of the
    padding behind them. The kernel's ``walk`` writes a path's nodes
    contiguously from slot 0, sets ``cost = INF_DIST`` exactly where a
    round failed (the path is then all -1 and ``hops`` 0), and ``hops``
    is the count of non-negative slots less one: the cost skips the
    failed rounds and the prefix is the filter
    (tests/test_ksp_kernel.py pins that contract). A caller with many
    jobs of one chunk hands ``costs`` and ``hops`` over as lists, turned
    into Python ints once."""
    inf = int(INF_DIST)
    out: list[tuple[int, list[str]]] = []
    for cost_row, hop_row, path_rows in zip(costs, hops, paths):
        c = int(cost_row[job])
        if c >= inf:
            continue
        ids = path_rows[job, : hop_row[job] + 1].tolist()
        ids.reverse()  # walk order is dest→root
        out.append((c, [node_names[n] for n in ids]))
    out.sort(key=lambda cp: (cp[0], cp[1]))
    return out
