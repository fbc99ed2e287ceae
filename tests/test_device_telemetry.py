"""Device telemetry plane tests (monitor/device.py, docs/Monitor.md
"Device telemetry"): kernel cost capture on the CPU backend, the
memory_stats degradation path, the efficiency join as a pure function,
the zero-extra-compile contract under the jit sanitizer, and the ctrl
export surface."""

import asyncio

import numpy as np
import pytest

from openr_tpu.monitor import Counters, compile_ledger
from openr_tpu.monitor import device as device_telemetry
from openr_tpu.monitor.device import (
    DeviceTelemetry,
    KernelCostRow,
    efficiency_rows,
    shard_rows,
)


def run(coro):
    return asyncio.run(coro)


def _small_solver(**kw):
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.utils.topogen import erdos_renyi_lsdb

    ls, ps, csr = erdos_renyi_lsdb(64, avg_degree=5, seed=2, max_metric=8)
    return TpuSpfSolver(native_rib="off", **kw), ls, ps, csr


# ------------------------------------------------------------- capture


def test_split_kernel_cost_captured_on_cpu():
    """The production split RIB solve must leave a cost/memory row for
    batched_sssp_split_rib: XLA's cost_analysis (flops, bytes) and
    memory_analysis (arg/out/temp bytes) are both CPU-available."""
    tel = device_telemetry.telemetry()
    tel.reset()
    tpu, ls, ps, _csr = _small_solver()
    tpu.compute_routes(ls, ps, "node-0")
    rows = tel.kernel_rows()
    assert "batched_sssp_split_rib" in rows
    row = rows["batched_sssp_split_rib"]
    assert row.error is None
    assert row.flops > 0
    assert row.bytes_accessed > 0
    assert row.arg_bytes > 0
    assert row.out_bytes > 0
    assert row.temp_bytes > 0
    assert row.resident_hbm_bytes >= (
        row.arg_bytes + row.out_bytes + row.temp_bytes
    )
    assert row.span == "spf:batched_solve"
    assert row.captures == 1


def test_export_to_counters_registry_names():
    tel = device_telemetry.telemetry()
    tel.reset()
    tpu, ls, ps, _csr = _small_solver()
    tpu.compute_routes(ls, ps, "node-0")
    c = Counters()
    device_telemetry.export_to(c)
    assert c.get("jax.kernel.batched_sssp_split_rib.flops") > 0
    assert c.get("jax.kernel.batched_sssp_split_rib.bytes_accessed") > 0
    assert c.get("jax.kernel.batched_sssp_split_rib.temp_bytes") > 0
    assert c.get("jax.kernel.batched_sssp_split_rib.captures") == 1


def test_observe_is_capture_once_per_compile():
    """Steady-state observe() is a probe, not a capture: the row's
    capture count stays 1 across repeated identical solves, and a
    genuinely new traced shape (fresh compile) recaptures."""
    tel = device_telemetry.telemetry()
    tel.reset()
    tpu, ls, ps, csr = _small_solver()
    tpu.compute_routes(ls, ps, "node-0")
    assert tel.kernel_rows()["batched_sssp_split_rib"].captures == 1
    tpu.compute_routes(ls, ps, "node-0")
    tpu.compute_routes(ls, ps, "node-0")
    assert tel.kernel_rows()["batched_sssp_split_rib"].captures == 1
    # a new batch bucket compiles a new variant of batched_sssp_split —
    # the ledger counts it, so observe recaptures exactly once
    before = tel.kernel_rows().get("batched_sssp_split")
    n_before = before.captures if before else 0
    roots = np.arange(8, dtype=np.int32) % csr.num_nodes
    tpu._solve_dist(csr, roots)
    tpu._solve_dist(csr, roots)
    after = tel.kernel_rows()["batched_sssp_split"]
    assert after.captures == n_before + 1


def test_capture_error_row_never_raises():
    tel = DeviceTelemetry()

    def bad_lower():
        raise RuntimeError("backend exploded")

    row = tel.capture("boom_kernel", bad_lower, span="spf:x")
    assert row.error is not None and "backend exploded" in row.error
    assert tel.kernel_rows()["boom_kernel"].captures == 1
    # error rows are excluded from the counter export
    c = Counters()
    tel.export_to(c)
    assert not any(k.startswith("jax.kernel.boom_kernel") for k in c.counters)


# -------------------------------------------------------- hbm gauges


def test_memory_stats_degrades_on_cpu():
    """CPU devices return None from memory_stats(): the first sample
    latches availability off, returns None, and stamps no device.*
    gauges; later calls are flag tests (no jax traffic needed)."""
    tel = DeviceTelemetry()
    c = Counters()
    assert tel.sample_hbm(c) is None
    assert tel.hbm_available is False
    assert not any(k.startswith("device.") for k in c.counters)
    assert tel.hbm_in_use_mb() is None
    # latched: a second sample takes the fast path and stays None
    assert tel.sample_hbm(c) is None


def test_hbm_transient_backend_error_does_not_latch(monkeypatch):
    """A backend-init failure must NOT permanently disable HBM gauges:
    only the genuine all-devices-report-no-stats shape (CPU) latches
    availability off (review finding)."""
    import jax

    tel = DeviceTelemetry()

    def boom():
        raise RuntimeError("backend init raced")

    monkeypatch.setattr(jax, "local_devices", boom)
    assert tel.sample_hbm() is None
    assert tel.hbm_available is None  # unlatched: next sample retries
    monkeypatch.undo()
    assert tel.sample_hbm() is None  # cpu: genuinely no stats...
    assert tel.hbm_available is False  # ...now latched


def test_dispatch_spans_are_separated_from_completion_spans():
    """_solve_dist's dispatch records under no span, never into the
    completion-walled spf:batched_solve stat the split RIB path owns
    (review finding: pooled sub-ms dispatch samples would drag that
    p50 under any real solve)."""
    tel = device_telemetry.telemetry()
    tel.reset()
    tpu, ls, ps, csr = _small_solver()
    tpu.compute_routes(ls, ps, "node-0")
    roots = np.arange(8, dtype=np.int32) % csr.num_nodes
    tpu._solve_dist(csr, roots)
    rows = tel.kernel_rows()
    assert rows["batched_sssp_split_rib"].span == "spf:batched_solve"
    assert rows["batched_sssp_split_rib"].span_complete is True
    assert rows["batched_sssp_split"].span is None


def test_annotate_boundary_makes_no_hbm_sample():
    """Leaving a profiling span records its stat and touches no device:
    the HBM gauges are sampled at rebuild edges only (decision.py)."""
    from openr_tpu.monitor import profiling

    c = Counters()
    with profiling.annotate("unit:test_span", counters=c):
        pass
    snap = c.snapshot()
    assert snap["profile.unit:test_span_ms.count"] == 1
    assert not any(k.startswith("device.") for k in c.counters)


# ------------------------------------------------- efficiency join


def test_efficiency_rows_pure_math():
    rows = {
        "k1": KernelCostRow(
            fn="k1", span="spf:batched_solve",
            flops=2e9, bytes_accessed=1e9, captures=1,
        ),
        "k2": KernelCostRow(fn="k2", span=None, flops=5.0, captures=1),
    }
    snap = {
        "profile.spf:batched_solve_ms.p50": 100.0,  # 0.1 s
        "profile.spf:batched_solve_ms.count": 7,
    }
    out = efficiency_rows(rows, snap)
    by_fn = {r["fn"]: r for r in out}
    # 2e9 flops / 0.1 s = 20 GFLOP/s; 1e9 bytes / 0.1 s = 10 GB/s
    assert by_fn["k1"]["achieved_gflops"] == pytest.approx(20.0)
    assert by_fn["k1"]["achieved_gbs"] == pytest.approx(10.0)
    assert by_fn["k1"]["span_count"] == 7
    # no span → no join, but the row still renders
    assert by_fn["k2"]["achieved_gflops"] is None
    assert by_fn["k2"]["span_p50_ms"] is None


def test_efficiency_rows_no_samples():
    rows = {"k": KernelCostRow(fn="k", span="spf:warm_solve", flops=1.0)}
    out = efficiency_rows(rows, {})
    assert out[0]["achieved_gflops"] is None


def test_efficiency_rows_dispatch_only_span_excluded():
    """A dispatch-only span (async return — e.g. the sharded solve)
    must report its p50 but NO achieved rate: full-kernel flops over
    dispatch wall would be unphysical (review finding)."""
    rows = {
        "k": KernelCostRow(
            fn="k", span="spf:sharded_solve", span_complete=False,
            flops=1e12, bytes_accessed=1e12,
        ),
    }
    snap = {"profile.spf:sharded_solve_ms.p50": 0.01}
    out = efficiency_rows(rows, snap)
    assert out[0]["span_p50_ms"] == 0.01
    assert out[0]["achieved_gflops"] is None
    assert out[0]["achieved_gbs"] is None
    assert out[0]["span_complete"] is False
    # the production sharded observe site marks itself dispatch-only
    tel = device_telemetry.telemetry()
    row = tel.kernel_rows().get("sharded_sssp_split")
    if row is not None:
        assert row.span_complete is False


# ------------------------------------------------------- shard rows


def _sharded_out(t, mesh, roots):
    import jax.numpy as jnp

    from openr_tpu.parallel import sharded_sssp_split

    return sharded_sssp_split(
        jnp.asarray(t["base_nbr"]), jnp.asarray(t["base_wgt"]),
        jnp.asarray(t["ov_ids"]), jnp.asarray(t["ov_nbr"]),
        jnp.asarray(t["ov_wgt"]), jnp.asarray(np.zeros(t["vp"], bool)),
        jnp.asarray(roots), mesh,
    )


def test_shard_rows_metadata_only():
    """Per-device layout of a sharded output without touching
    shard.data (conftest forces 8 virtual CPU devices)."""
    import jax

    from openr_tpu.ops.spf_split import build_split_tables
    from openr_tpu.parallel import make_mesh
    from openr_tpu.utils import topogen

    es, ed, em, _vpc, nn, _ne = topogen.erdos_renyi_csr(
        96, avg_degree=5, seed=4, max_metric=8
    )
    t = build_split_tables(es, ed, em, nn)
    mesh = make_mesh(
        n_sources=2, n_graph=2, devices=jax.devices("cpu")[:4]
    )
    out = _sharded_out(t, mesh, np.arange(8, dtype=np.int32) % nn)
    rows = shard_rows(out)
    assert len(rows) == 4
    assert [r["device"] for r in rows] == sorted(r["device"] for r in rows)
    for r in rows:
        # output spec is P(None, sources): rows replicated, batch split
        assert r["shard_shape"] == [t["vp"], 4]
        assert r["shard_bytes"] == t["vp"] * 4 * np.dtype(np.int32).itemsize
    # mesh solves through the solver also keep the layout for ctrl
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.utils.topogen import erdos_renyi_lsdb

    ls, _ps, csr = erdos_renyi_lsdb(96, avg_degree=5, seed=4, max_metric=8)
    solver = TpuSpfSolver(native_rib="off", mesh=mesh)
    solver._solve_dist(csr, np.arange(8, dtype=np.int32) % csr.num_nodes)
    assert len(solver.last_shard_rows) == 4


def test_shard_rows_unsharded_degrades():
    assert shard_rows(object()) == []


# --------------------------------------- steady-state compile gate


@pytest.mark.jit_steady_state
def test_capture_adds_zero_steady_state_compiles():
    """The telemetry capture path itself must not compile: after
    warmup + captures, repeat solves (whose observe() probes run every
    time) land zero XLA compiles — the conftest jit sanitizer fails
    this test on any post-mark_warm compile."""
    tel = device_telemetry.telemetry()
    tel.reset()
    tpu, ls, ps, _csr = _small_solver()
    tpu.compute_routes(ls, ps, "node-0")  # trace + compile + capture
    tpu.compute_routes(ls, ps, "node-0")  # warm
    compile_ledger.mark_warm()
    for _ in range(3):
        tpu.compute_routes(ls, ps, "node-0")
    assert tel.kernel_rows()["batched_sssp_split_rib"].captures == 1


# ------------------------------------------------------ ctrl export


def test_ctrl_get_device_telemetry():
    from openr_tpu.emulator import Cluster
    from openr_tpu.rpc import RpcClient

    # seed one process-wide kernel row (the emulated nodes run the cpu
    # oracle, which never jits)
    tel = device_telemetry.telemetry()
    tel.reset()
    tpu, ls, ps, _csr = _small_solver()
    tpu.compute_routes(ls, ps, "node-0")

    async def body():
        c = Cluster.from_edges([("a", "b")], enable_ctrl=True)
        await c.start()
        try:
            await c.wait_converged(timeout=30)
            cli = RpcClient(port=c.nodes["a"].ctrl.port)
            await cli.connect()
            try:
                return await cli.call("get_device_telemetry", {})
            finally:
                await cli.close()
        finally:
            await c.stop()

    res = run(body())
    assert res["node"] == "a"
    assert res["hbm_available"] is False
    assert res["devices"] == []
    fns = {k["fn"] for k in res["kernels"]}
    assert "batched_sssp_split_rib" in fns
    row = next(
        k for k in res["kernels"] if k["fn"] == "batched_sssp_split_rib"
    )
    assert row["flops"] > 0
    # the oracle-backed node has no solver spans, so the join degrades
    # to unjoined rows rather than failing
    assert "achieved_gflops" in row


# ------------------------------------------------------ soak sample


def test_soak_round_sample_carries_hbm_field():
    from openr_tpu.emulator.soak import RoundSample, SoakConfig

    assert SoakConfig.hbm_slack_mb > 0
    s = RoundSample(
        round=0, rss_mb=None, objects=0, churn_events=0, schedule_hash="x"
    )
    assert s.hbm_mb is None
