"""Fleet solve: every node's RouteDatabase from ONE batched device call.

reference: the reference has no equivalent — each router runs its own
SpfSolver (openr/decision/SpfSolver.cpp †), so an N-node simulation
pays N sequential Dijkstra passes. The TPU kernel's batch dimension
makes the fleet shape *native*: solve SSSP from ALL nodes at once
(the relax sweep is gather-row bound, so widening the batch is nearly
free — docs/spf_kernel_profile.md), then derive each node's ECMP
first-hop matrix from the shared distance matrix by the same
elementwise identity `first_hop_matrix` uses, entirely in host numpy
(no per-node device dispatch).

Used by the emulator for whole-cluster RIB validation and by
benchmarks/bench_fleet.py (BASELINE configs 1-2 routes/sec at fleet
scale). Per-node equality with `TpuSpfSolver.compute_routes` is
asserted in tests/test_fleet.py.
"""

from __future__ import annotations

import numpy as np

from openr_tpu.ops.spf import INF_DIST, METRIC_MAX, pad_batch
from openr_tpu.types.routes import RouteDatabase


def compute_fleet_ribs(
    ls,
    ps,
    nodes: list[str] | None = None,
    solver=None,
    chunk: int = 256,
) -> dict[str, RouteDatabase]:
    """RouteDatabases for every node in `nodes` (default: all nodes in
    the topology) from batched all-roots solves, chunked at `chunk`
    roots so the [Vp, D, B] relax intermediate stays bounded at fleet
    scale, with the previous chunk's device→host copy overlapping the
    next chunk's solve."""
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    if solver is None:
        solver = TpuSpfSolver(native_rib="off")
    if solver.enable_lfa:
        raise ValueError(
            "compute_fleet_ribs does not assemble LFA backups; use the "
            "per-node TpuSpfSolver(enable_lfa=True) path"
        )
    csr = ls.to_csr()
    n = csr.num_nodes
    if n == 0:
        return {}

    # per-node out-adjacency (min metric per neighbor), from the edges
    # the CSR carries details of for nexthop construction
    nbrs_of: dict[int, list[int]] = {}
    src, dst = csr.detail_pairs()
    for s, d in zip(src.tolist(), dst.tolist()):
        nbrs_of.setdefault(s, []).append(d)

    targets = [
        node
        for node in (nodes if nodes is not None else list(csr.node_names))
        if node in csr.name_to_id
    ]
    # roots actually needed: each target plus its neighbors (a subset
    # request must not pay a whole-fleet solve)
    needed: set[int] = set()
    for node in targets:
        mid = csr.name_to_id[node]
        needed.add(mid)
        needed.update(nbrs_of.get(mid, []))
    if not targets:
        return {}
    root_list = np.array(sorted(needed), dtype=np.int32)
    col_of = {int(r): i for i, r in enumerate(root_list)}

    chunk = pad_batch(min(chunk, max(len(root_list), 1)))
    cols = []
    pending = None
    for start in range(0, len(root_list), chunk):
        roots = np.resize(root_list[start : start + chunk], chunk)
        d = solver._solve_dist(csr, roots)
        if pending is not None:
            cols.append(np.asarray(pending))
        pending = d
    cols.append(np.asarray(pending))
    dist_all = np.concatenate(cols, axis=1)[:, : len(root_list)]

    # The assembly caches are kept per root slot fingerprint; raise the
    # cap DURABLY so repeated fleet passes keep their entries (cross-pass
    # reuse is why a caller shares a solver at all). The memory cost is
    # the caller's explicit choice: the default (solver=None) footprint
    # dies with this call, and a shared solver can reclaim it any time
    # via TpuSpfSolver.trim_caches().
    solver._slots.cap = max(solver._slots.cap, len(targets) + 1)
    return _assemble_all(
        solver, ls, ps, csr, targets, nbrs_of, col_of, dist_all
    )


def _assemble_all(
    solver, ls, ps, csr, targets, nbrs_of, col_of, dist_all
) -> dict[str, RouteDatabase]:
    out: dict[str, RouteDatabase] = {}
    for node in targets:
        my_id = csr.name_to_id.get(node)
        if my_id is None:
            continue
        nbr_ids = sorted(nbrs_of.get(my_id, []))
        k = len(nbr_ids)
        b = pad_batch(1 + k)
        nbr_metric = np.empty(k, dtype=np.int64)
        for i, d in enumerate(nbr_ids):
            nbr_metric[i] = min(
                min(det[1] for det in csr.details(my_id, d)), METRIC_MAX
            )
        d_root = dist_all[:, col_of[my_id]].astype(np.int64)  # [vp]
        d_nbr = dist_all[
            :, [col_of[d] for d in nbr_ids]
        ].astype(np.int64)  # [vp, k]
        # ECMP first-hop identity (ops.spf.first_hop_matrix, host-side):
        # n is a valid first hop toward v iff m(root,n) + dist_n(v) ==
        # dist_root(v); overloaded neighbors only toward themselves
        reach = (d_root[:, None] < INF_DIST) & (d_nbr < INF_DIST)
        on_spt = reach & (nbr_metric[None, :] + d_nbr == d_root[:, None])
        if k:
            nbr_over = csr.node_overloaded[np.array(nbr_ids)]
            dest_is_nbr = (
                np.arange(dist_all.shape[0])[:, None]
                == np.array(nbr_ids)[None, :]
            )
            on_spt &= ~nbr_over[None, :] | dest_is_nbr
        fh = np.zeros((b - 1, dist_all.shape[0]), dtype=bool)
        fh[:k] = on_spt.T
        solved = (
            csr,
            dist_all[:, col_of[my_id]][:, None].astype(np.int32),
            fh,
            nbr_ids,
            None,
        )
        rdb = RouteDatabase(this_node_name=node)
        out[node] = solver._assemble_routes(rdb, ls, ps, node, solved)
    return out
