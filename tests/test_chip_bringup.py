"""Bring-up contract tests (PR 21): nothing on the served path hides
which device solved, no entry point carries on on the CPU when it was
started for the chip, the compile cache is placed from outside, and
chip_smoke.py's CPU rehearsal runs the command the chip runs.

These run on the CPU-only tier-1 host; the chip itself is checked by
`python chip_smoke.py` through the chip tool (CHANGES.md PR 21).
"""

import asyncio
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.monitor import compile_ledger
from openr_tpu.ops.native_spf import native_available
from openr_tpu.utils import topogen
from tests.test_decision import adj_pub, mk_decision, prefix_pub

REPO = Path(__file__).resolve().parent.parent


def _run(cmd, env_set=None, env_unset=(), timeout=300):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child decides its own device count
    for k in env_unset:
        env.pop(k, None)
    env.update(env_set or {})
    return subprocess.run(
        [sys.executable, *cmd], cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True,
    )


# ------------------------------------------------------------ chip_smoke.py


def test_chip_smoke_tiny_cpu_rehearsal_passes():
    r = _run(["chip_smoke.py", "--allow-cpu", "--tiny"])
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the driver's contract: these keys and no other on the last line
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"  # never mistakable for a chip
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    tag = "[platform: cpu] report: "
    assert lines[-2].startswith(tag)
    out = json.loads(lines[-2][len(tag):])
    assert out["ok"] is True and out["device"] == last["device"]
    assert out["mode"] == "tiny"
    assert set(out["legs"]) == {"A", "B", "C", "D"}
    for leg in "ABC":
        assert out["legs"][leg]["ok"] is True, out["legs"][leg]
        assert out["legs"][leg]["engine"] == "device"
    assert out["legs"]["D"]["real_devices"] is False
    steps = {s["step"]: s for s in out["legs"]["B"]["steps"]}
    assert steps["flap2_raise"]["path"] == "topo_delta"
    assert steps["flap2_raise"]["compiles"] == 0
    assert steps["link_down"]["path"] == "full"
    # every line says which platform produced it
    assert all(ln.startswith("[platform: cpu]") for ln in lines[:-1])


def test_chip_smoke_refuses_a_host_without_a_chip():
    r = _run(["chip_smoke.py"], env_set={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout == ""  # no result of any kind
    assert "platform 'cpu'" in r.stderr


# ------------------------------------------------- bench.py / __graft_entry__


def test_bench_exits_nonzero_without_a_chip():
    r = _run(["bench.py"], env_set={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "tpu_" not in r.stdout and r.stdout.strip() == ""
    assert "platform 'cpu'" in r.stderr


def test_graft_entry_refuses_silent_cpu_fallback():
    # no JAX_PLATFORMS: jax finds no chip here and falls back to the CPU
    r = _run(["__graft_entry__.py", "--entry"], env_unset=("JAX_PLATFORMS",))
    assert r.returncode != 0
    assert "expected platform tpu" in r.stderr
    # asked for explicitly, the CPU is a legitimate answer
    r = _run(
        ["__graft_entry__.py", "--entry"], env_set={"JAX_PLATFORMS": "cpu"}
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "entry ok" in r.stdout


# ------------------------------------------------------------ compile cache

_PRINT_CACHE_DIR = (
    "import openr_tpu.ops, jax; print(jax.config.jax_compilation_cache_dir)"
)


def test_compile_cache_defaults_to_the_checkout():
    r = _run(
        ["-c", _PRINT_CACHE_DIR],
        env_set={"JAX_PLATFORMS": "cpu"},
        env_unset=("JAX_COMPILATION_CACHE_DIR",),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(REPO / ".jax_cache")


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    r = _run(
        ["-c", _PRINT_CACHE_DIR],
        env_set={
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        },
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)
    assert not (tmp_path / ".jax_cache").exists()


# ------------------------------------------- the engine is observable


def _fat_tree_lsdb(k=4):
    adj_dbs, prefix_dbs = topogen.fat_tree(k)
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(db)
    return ls, ps, adj_dbs[0].this_node_name


def test_solver_names_its_device_and_counts_solves_per_engine(caplog):
    ls, ps, me = _fat_tree_lsdb()
    with caplog.at_level("INFO", logger="openr_tpu.decision.spf_backend"):
        dev = TpuSpfSolver(native_rib="off")
    assert dev.platform == "cpu" and dev.device_kind
    assert any(
        "platform=cpu" in r.getMessage() and "device_kind=" in r.getMessage()
        for r in caplog.records
    )
    dev.compute_routes(ls, ps, me)
    assert dev.spf_kernel_stats["engine_device"] == 1
    assert dev.spf_kernel_stats["engine_native"] == 0
    if native_available():
        nat = TpuSpfSolver(native_rib="on")
        nat.compute_routes(ls, ps, me)
        assert nat.spf_kernel_stats["engine_native"] == 1
        assert nat.spf_kernel_stats["engine_device"] == 0


def test_decision_surfaces_a_rebuild_whose_solver_raises():
    async def body():
        d, pubs, routes = mk_decision(backend="tpu")
        await d.start()
        adj_dbs, prefix_dbs = topogen.ring(4)
        pubs.push(adj_pub(adj_dbs))
        pubs.push(prefix_pub(prefix_dbs))
        await asyncio.wait_for(routes.get(), 30)
        assert d.last_rebuild_error is None
        assert d.counters.get("decision.rebuild.failed") == 0
        rib_before = dict(d.rib.unicast_routes)

        def boom(*_a, **_k):
            raise RuntimeError("XLA says no: Mosaic failed to compile")

        d._tpu.compute_routes = boom
        d._tpu.warm_compute_routes = boom
        flapped = list(adj_dbs)
        flapped[1] = topogen.ring(4, metric=7)[0][1]
        pubs.push(adj_pub([flapped[1]], version=2))
        for _ in range(200):
            if d.last_rebuild_error is not None:
                break
            await asyncio.sleep(0.02)
        assert d.last_rebuild_error == (
            "RuntimeError: XLA says no: Mosaic failed to compile"
        )
        assert d.counters.get("decision.rebuild.failed") >= 1
        # the old RIB keeps being served
        assert dict(d.rib.unicast_routes) == rib_before
        await d.stop()

    asyncio.run(body())


def test_compile_ledger_counts_under_the_bare_function_name():
    """jax 0.9.0 logs "Compiling jit(<fn>) ..."; the 0.4.x-era pattern
    matched nothing, so every zero-steady-state-compile gate was vacuous."""

    @jax.jit
    def bringup_ledger_probe(x):
        return x * 3 + 1

    before = compile_ledger.compiles_of("bringup_ledger_probe")
    bringup_ledger_probe(jnp.ones(7)).block_until_ready()
    assert compile_ledger.compiles_of("bringup_ledger_probe") == before + 1
    bringup_ledger_probe(jnp.ones(7)).block_until_ready()
    assert compile_ledger.compiles_of("bringup_ledger_probe") == before + 1


# ------------------------------------- aux benches stand on their own


@pytest.mark.parametrize(
    "path", ["benchmarks/bench_fleet.py", "benchmarks/bench_ksp_lfa.py"]
)
def test_aux_benches_no_longer_reach_into_bench_py(path):
    src = (REPO / path).read_text()
    assert "acquire_bench_lock" not in src and "import bench\n" not in src
