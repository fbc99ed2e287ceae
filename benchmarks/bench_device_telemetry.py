"""Device-telemetry smoke (`ci.sh` lane): the kernel cost ledger must
capture a cost/memory row for every canonical jitted kernel entry point
on the CPU backend, telemetry must export through ctrl, and the capture
path must add ZERO steady-state compiles (docs/Monitor.md "Device
telemetry").

Exercises each canonical entry point the way its production consumer
does — the split RIB solve via ``TpuSpfSolver.compute_routes``, the
batched kernel via ``_solve_dist``, the sharded kernel via a 2x2 mesh
solver, and the election / KSP wrappers with production-shaped small
inputs — then warms the compile ledger and
re-runs everything: any post-warmup XLA compile (including one caused
by the telemetry captures themselves) exits 1.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# the sharded section needs a multi-device CPU mesh: force the virtual
# device count BEFORE jax initializes (same dance as __graft_entry__)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

#: every canonical jitted kernel entry point must own a captured row
EXPECTED_KERNELS = (
    "batched_sssp_split_rib",   # fused split RIB solve (production path)
    "batched_sssp_split",       # batched split kernel (_solve_dist)
    "sharded_sssp_split",       # mesh-sharded split kernel
    "_elect_seg",               # device election segmented reductions
    "_ksp_edge_disjoint_dense_jit",  # k-shortest-paths kernel
)


def _fail(msg: str) -> None:
    print(f"DEVICE-TELEMETRY SMOKE FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _run_kernels() -> None:
    """One call through every canonical entry point (compiles on the
    first pass, pure cache hits on the steady-state pass)."""
    import jax

    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.ops.ksp import build_ksp_blocked, ksp_edge_disjoint_dense
    from openr_tpu.parallel import make_mesh
    from openr_tpu.utils.topogen import erdos_renyi_lsdb

    ls, ps, csr = erdos_renyi_lsdb(96, avg_degree=6, seed=3, max_metric=16)

    # production split RIB solve (batched_sssp_split_rib)
    tpu = TpuSpfSolver(native_rib="off")
    tpu.compute_routes(ls, ps, "node-0")

    # batched split kernel via the dispatch seam the fleet pass uses
    roots = np.arange(8, dtype=np.int32) % csr.num_nodes
    tpu._solve_dist(csr, roots)

    # sharded split kernel over a 2x2 CPU mesh
    mesh = make_mesh(
        n_sources=2, n_graph=2, devices=jax.devices("cpu")[:4]
    )
    sharded = TpuSpfSolver(native_rib="off", mesh=mesh)
    b16 = np.arange(16, dtype=np.int32) % csr.num_nodes
    sharded._solve_dist(csr, b16)

    # device election (segmented reductions) on a tiny 2-advertiser
    # anycast matrix — the dispatch-threshold route is covered by
    # tests; the smoke wants the kernel row
    from openr_tpu.decision.election import MultiTable
    from openr_tpu.types.network import IpPrefix

    t = MultiTable(
        prefixes=[IpPrefix.make("10.9.0.0/32")],
        indptr=np.array([0, 2], np.int64),
        seg=np.zeros(2, np.int64),
        adv=np.array([1, 2], np.int64),
        known=np.ones(2, bool),
        rank=np.array([0, 1], np.int64),
        entries=[None, None],
        names=["node-1", "node-2"],
    )
    from openr_tpu.ops.election import elect_multi_device

    d_vec = np.arange(csr.padded_nodes, dtype=np.int64) + 1
    reach = np.ones(csr.padded_nodes, bool)
    elect_multi_device(t, d_vec, reach, 0, dev_cache={}, gen=0)

    # KSP kernel through its canonicalizing wrapper
    nbr, wgt = csr.dense_tables()
    blocked = build_ksp_blocked(nbr, csr.node_overloaded, 0)
    dests = np.arange(4, dtype=np.int32) % csr.num_nodes
    ksp_edge_disjoint_dense(
        nbr, wgt, blocked, 0, dests, k=2, max_hops=csr.padded_nodes
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.parse_args()

    from openr_tpu.monitor import compile_ledger
    from openr_tpu.monitor import device as device_telemetry

    led = compile_ledger.install()
    import jax

    if jax.default_backend() != "cpu":
        _fail(f"lane must run on cpu, got {jax.default_backend()}")

    _run_kernels()

    rows = device_telemetry.kernel_rows()
    missing = [k for k in EXPECTED_KERNELS if k not in rows]
    if missing:
        _fail(f"no cost row captured for: {missing} (have {sorted(rows)})")
    bad = [
        k
        for k in EXPECTED_KERNELS
        if rows[k].error is not None
        or rows[k].flops <= 0
        or rows[k].bytes_accessed <= 0
    ]
    if bad:
        detail = {k: rows[k].to_jsonable() for k in bad}
        _fail(f"degenerate cost rows: {detail}")

    # steady state: the SAME calls again — every kernel is a jit cache
    # hit and every telemetry observe() is a dict probe; any compile
    # (including one a capture would cause) fails the lane
    led.mark_warm()
    _run_kernels()
    steady = led.compiles_since_warm()
    if steady:
        _fail(f"steady-state compiles after warmup: {steady}")

    # ctrl export: a live node's get_device_telemetry must serve the
    # process-wide rows joined with its span stats, HBM degraded on cpu
    import asyncio

    from openr_tpu.emulator import Cluster
    from openr_tpu.rpc import RpcClient

    async def ctrl_check() -> dict:
        c = Cluster.from_edges([("a", "b")], enable_ctrl=True)
        await c.start()
        try:
            await c.wait_converged(timeout=60)
            cli = RpcClient(port=c.nodes["a"].ctrl.port)
            await cli.connect()
            try:
                return await cli.call("get_device_telemetry", {})
            finally:
                await cli.close()
        finally:
            await c.stop()

    res = asyncio.run(ctrl_check())
    served = {k["fn"] for k in res.get("kernels", [])}
    if not set(EXPECTED_KERNELS) <= served:
        _fail(
            f"ctrl get_device_telemetry missing kernels: "
            f"{set(EXPECTED_KERNELS) - served}"
        )
    if res.get("hbm_available") is not False or res.get("devices"):
        _fail(
            "cpu backend must degrade hbm telemetry "
            f"(got hbm_available={res.get('hbm_available')}, "
            f"devices={res.get('devices')})"
        )

    print(
        f"device-telemetry smoke ok: {len(rows)} kernel cost rows "
        f"({', '.join(sorted(k for k in EXPECTED_KERNELS))}), "
        f"0 steady-state compiles, ctrl export ok, hbm degraded on cpu"
    )


if __name__ == "__main__":
    main()
