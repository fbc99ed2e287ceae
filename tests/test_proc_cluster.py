"""Multi-process supervisor regressions (openr_tpu/emulator/procs.py,
docs/Emulator.md "Multi-process clusters"): readiness-handshake
fail-fast on bind collisions, TCP kvstore reconnect across a hard
kill+restart (`kvstore.peer_reconnects`), and the graceful-restart
re-handshake across real process boundaries — the restarted process
binds new ephemeral ports, so peers must re-learn endpoints from the
fresh handshake, never from pre-restart cache."""

import asyncio
import json
import signal
import socket
import sys

import pytest

from openr_tpu.emulator import proc_invariants
from openr_tpu.emulator.cluster import LinkSpec
from openr_tpu.emulator.procs import ProcCluster, ProcNode
from openr_tpu.rpc import RpcClient


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _node_cfg(name, ctrl, kv, udp_local, udp_peer, loopback):
    # long spark hold: the kill/restart window below must be a kvstore
    # session break, NOT a hold-timer adjacency loss — the TCP
    # reconnect path is what's under test
    return {
        "node_name": name,
        "ctrl_port": ctrl,
        "kvstore_port": kv,
        "endpoint_host": "127.0.0.1",
        "spark": {
            "hello_time_ms": 500,
            "fastinit_hello_time_ms": 100,
            "handshake_time_ms": 100,
            "keepalive_time_ms": 250,
            "hold_time_ms": 60000,
            "graceful_restart_time_ms": 60000,
        },
        "kvstore": {"initial_sync_grace_s": 0.5},
        "decision": {"use_tpu_solver": False},
        "udp_interfaces": [
            {
                "if_name": f"udp-{name}",
                "local_port": udp_local,
                "peer_host": "127.0.0.1",
                "peer_port": udp_peer,
            }
        ],
        "originated_prefixes": [{"prefix": loopback}],
    }


async def _spawn(cfg_path, log_file, ready=None):
    argv = [
        sys.executable, "-m", "openr_tpu",
        "--config", str(cfg_path), "--log-level", "WARNING",
        "--jax-platform", "cpu",
    ]
    if ready:
        argv += ["--ready-file", str(ready)]
    return await asyncio.create_subprocess_exec(
        *argv, stdout=log_file, stderr=log_file
    )


async def _ctrl_call(port, method, params=None, timeout=10.0):
    cli = RpcClient(host="127.0.0.1", port=port)
    await cli.connect(timeout=timeout)
    try:
        return await cli.call(method, params or {}, timeout=timeout)
    finally:
        await cli.close()


async def _poll(what, predicate, timeout=90.0, interval=0.5):
    deadline = asyncio.get_event_loop().time() + timeout
    last = None
    while asyncio.get_event_loop().time() < deadline:
        try:
            last = await predicate()
        except OSError:
            last = None  # ctrl not back up yet
        if last:
            return last
        await asyncio.sleep(interval)
    raise AssertionError(f"{what} never satisfied (last={last!r})")


def test_child_that_ignores_sigterm_is_killed_not_waited_for():
    """crash_node(graceful=True) and stop() SIGTERM a child and collect
    it. A child that never acts on the signal used to be waited for
    without end (in a thread, which `asyncio.run` then waited for on its
    way out too): it has a deadline now, and is killed at it."""
    import subprocess
    import time

    child = subprocess.Popen(
        [
            sys.executable, "-c",
            "import signal, time; "
            "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
            "print('ready', flush=True); time.sleep(600)",
        ],
        stdout=subprocess.PIPE,
    )
    try:
        assert child.stdout.readline() == b"ready\n"  # handler installed
        pn = ProcNode("deaf", "", "", "", proc=child)
        child.send_signal(signal.SIGTERM)
        t0 = time.monotonic()
        asyncio.run(ProcCluster._reap(pn, t0 + 0.5))
        assert not pn.alive
        assert child.returncode == -signal.SIGKILL
        assert 0.5 <= time.monotonic() - t0 < 5.0
    finally:
        child.kill()
        child.stdout.close()


@pytest.mark.timeout(60)
def test_bind_collision_fails_fast(tmp_path):
    """Satellite contract (docs/Emulator.md): a pinned-port collision
    must kill the child with an {'error': ...} ready file and rc=1 —
    never a half-up daemon the supervisor waits on forever."""

    async def main():
        squat = socket.socket()
        squat.bind(("127.0.0.1", 0))
        squat.listen(1)
        taken = squat.getsockname()[1]
        kv, udp_a, udp_b = _free_ports(3)
        cfg = tmp_path / "collide.json"
        await asyncio.to_thread(cfg.write_text, json.dumps(
            _node_cfg("collide", taken, kv, udp_a, udp_b, "10.98.0.1/32")
        ))
        ready = tmp_path / "collide.ready.json"
        lf = await asyncio.to_thread(  # noqa: SIM115
            open, str(tmp_path / "collide.log"), "wb"
        )
        try:
            proc = await _spawn(cfg, lf, ready=ready)
            try:
                rc = await asyncio.wait_for(proc.wait(), 30)
            finally:
                if proc.returncode is None:
                    proc.kill()
                squat.close()
        finally:
            lf.close()
        assert rc == 1
        handshake = json.loads(await asyncio.to_thread(ready.read_text))
        assert "error" in handshake
        assert handshake["node"] == "collide"

    asyncio.run(main())


@pytest.mark.timeout(150)
def test_kill_restart_reconnects_same_peer(tmp_path):
    """SIGKILL one of two daemons mid-adjacency and bring it back on the
    SAME pinned ports: the survivor's kvstore session breaks (RST /
    ECONNREFUSED under ExponentialBackoff retries), and the eventual
    re-sync must be counted as kvstore.peer_reconnects — plus full
    re-convergence. The count holds whichever way Spark sees the fresh
    instance first: by its unsolicited handshake (NEIGHBOR_RESTARTED,
    the kvstore peer object stays) or by its first hello, which does
    not hear us yet (NEIGHBOR_DOWN then NEIGHBOR_UP, the peer object is
    rebuilt) — which packet wins is a race the survivor cannot steer."""

    async def main():
        ctrl_a, ctrl_b, kv_a, kv_b, udp_a, udp_b = _free_ports(6)
        cfg_a = tmp_path / "a.json"
        cfg_b = tmp_path / "b.json"
        await asyncio.to_thread(cfg_a.write_text, json.dumps(_node_cfg(
            "proc-a", ctrl_a, kv_a, udp_a, udp_b, "10.98.1.1/32")))
        await asyncio.to_thread(cfg_b.write_text, json.dumps(_node_cfg(
            "proc-b", ctrl_b, kv_b, udp_b, udp_a, "10.98.1.2/32")))

        async def synced_and_programmed(port):
            async def check():
                st = await _ctrl_call(port, "get_convergence_state")
                if not st.get("initialized"):
                    return None
                peers = st.get("peers") or []
                if not peers or not all(p.get("synced") for p in peers):
                    return None
                # the other node's loopback made it down the pipeline
                return (st.get("fib") or {}).get("programmed_unicast", 0) >= 1
            return await _poll(f"convergence on :{port}", check)

        procs = {}
        logs = []
        try:
            for name, cfg in (("a", cfg_a), ("b", cfg_b)):
                lf = await asyncio.to_thread(  # noqa: SIM115
                    open, str(cfg) + ".log", "wb"
                )
                logs.append(lf)
                procs[name] = await _spawn(cfg, lf)
            await synced_and_programmed(ctrl_a)
            await synced_and_programmed(ctrl_b)
            base = await _ctrl_call(
                ctrl_a, "get_counters", {"prefix": "kvstore.peer_reconnects"}
            )
            assert base.get("kvstore.peer_reconnects", 0) == 0

            procs["b"].send_signal(signal.SIGKILL)
            await procs["b"].wait()

            # advertisements force floods at the dead session — the
            # survivor must notice, tear the session down, and enter
            # retry backoff against the still-held peer. More than one
            # may be needed: the first write after the peer died can
            # land in the socket buffer before the RST comes back, so
            # only a LATER flood raises
            adv_seq = iter(range(100, 160))

            async def session_broken():
                await _ctrl_call(
                    ctrl_a, "advertise_prefixes",
                    {"prefixes": [f"10.98.1.{next(adv_seq)}/32"]},
                )
                st = await _ctrl_call(ctrl_a, "get_convergence_state")
                peers = st.get("peers") or []
                return bool(peers) and any(not p["synced"] for p in peers)

            await _poll(
                "session break on proc-a", session_broken,
                timeout=60, interval=1.0,
            )

            lf = await asyncio.to_thread(  # noqa: SIM115
                open, str(cfg_b) + ".restart.log", "wb"
            )
            logs.append(lf)
            procs["b"] = await _spawn(cfg_b, lf)

            await synced_and_programmed(ctrl_a)
            await synced_and_programmed(ctrl_b)
            after = await _ctrl_call(
                ctrl_a, "get_counters", {"prefix": "kvstore.peer_reconnects"}
            )
            assert after.get("kvstore.peer_reconnects", 0) >= 1
        finally:
            for p in procs.values():
                if p.returncode is None:
                    p.terminate()
            for p in procs.values():
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except asyncio.TimeoutError:
                    p.kill()
            for lf in logs:
                lf.close()

    asyncio.run(main())


@pytest.mark.timeout(240)
def test_proc_cluster_sigkill_warm_boot_parity(tmp_path):
    """Crash-recovery invariant (class 7, docs/Persist.md) across a
    REAL process crash: snapshot the victim's durable book digests at
    quiescence, arm a torn write, drive one doomed advertisement (it
    applies in memory, floods, and wedges the journal mid-frame), then
    SIGKILL. The re-exec'd incarnation must truncate the torn tail and
    recover byte-identical pre-crash state, while survivors — whose
    hold timers outlive the restart — observe zero withdrawal window
    (no key expiry, no neighbor_down)."""

    async def main():
        links = [
            LinkSpec("node-0", "node-1"),
            LinkSpec("node-1", "node-2"),
        ]
        cluster = ProcCluster(
            links, workdir=str(tmp_path), prefixes_per_node=2,
            # hold/GR must outlive the SIGKILL→ready window or the
            # zero-withdrawal half of the invariant is unsatisfiable
            spark_overrides={
                "hold_time_ms": 60000,
                "graceful_restart_time_ms": 60000,
            },
        )
        try:
            await cluster.start()
            await proc_invariants.wait_quiescent(
                cluster, timeout_s=120, context="persist cold boot"
            )
            pre = await proc_invariants.snapshot_persist(cluster, "node-2")
            assert pre["books"], "no durable books at quiescence"
            assert set(pre["watch"]) == {"node-0", "node-1"}

            res = await cluster.inject_disk_fault("node-2", "torn", at=3)
            assert res["ok"], res
            # the doomed mutation: applies in memory + floods to peers,
            # but its journal frame tears at byte 3 and wedges the
            # journal — the crash model where the writer believes the
            # write succeeded
            await cluster.call(
                "node-2", "advertise_prefixes",
                {"prefixes": ["10.97.255.1/32"]},
            )

            async def wedged():
                st = await cluster.get_persist_status("node-2")
                return st.get("wedged") or None

            await _poll("journal wedged on node-2", wedged, timeout=30)

            # announce GR, then SIGKILL: peers park the adjacency in
            # RESTART (no NEIGHBOR_DOWN — the zero-withdrawal half),
            # while the process still dies hard with the torn frame on
            # disk (an unannounced kill is CORRECTLY flapped by Spark's
            # non-GR restart detection, so it can't be hitless)
            await cluster.call("node-2", "spark_announce_restart")
            await cluster.crash_node("node-2")  # SIGKILL, nothing flushed
            await cluster.restart_node("node-2")
            await proc_invariants.wait_quiescent(
                cluster, timeout_s=120, context="persist warm boot"
            )
            violations = await proc_invariants.check_persist_recovery(
                cluster, pre
            )
            assert not violations, [str(v) for v in violations]

            post = await cluster.get_persist_status("node-2")
            rec = post["recovery"]
            # evidence the fault actually bit: the torn frame was found
            # and truncated at boot, and real records came off disk
            assert rec["truncated_bytes"] > 0
            assert rec["snapshot_records"] + rec["journal_records"] > 0
            assert not post["wedged"]
        finally:
            await cluster.stop()

    asyncio.run(main())


@pytest.mark.timeout(240)
def test_proc_cluster_graceful_restart_rehandshake(tmp_path):
    """3-process line via the supervisor: graceful restart of an end
    node rebinds every listener on NEW ephemeral ports, so the
    surviving peer must re-learn kvstore/ctrl endpoints from the fresh
    Spark handshake (the GR re-establishment path). wait_quiescent
    then demands the full cross-process invariant suite twice in a
    row — a peer stuck re-syncing a dead pre-restart endpoint would
    saturate its backoff and fail the stuck-state check."""

    async def main():
        links = [
            LinkSpec("node-0", "node-1"),
            LinkSpec("node-1", "node-2"),
        ]
        cluster = ProcCluster(
            links, workdir=str(tmp_path), prefixes_per_node=2
        )
        try:
            await cluster.start()
            await proc_invariants.wait_quiescent(
                cluster, timeout_s=120, context="proc 3-line cold"
            )
            await cluster.crash_node("node-2", graceful=True)
            await asyncio.sleep(1.0)
            old_ports = (
                cluster.crashed["node-2"].ready["kvstore_port"],
                cluster.crashed["node-2"].ready["ctrl_port"],
            )
            await cluster.restart_node("node-2")
            new_ports = (
                cluster.nodes["node-2"].ready["kvstore_port"],
                cluster.nodes["node-2"].ready["ctrl_port"],
            )
            # ephemeral binding makes the endpoint-move real: if this
            # ever collides, the test is not exercising the GR path
            assert new_ports != old_ports
            await proc_invariants.wait_quiescent(
                cluster, timeout_s=120, context="proc 3-line GR restart"
            )
            # node-1 must have re-peered node-2 at its NEW endpoint
            st = await cluster.call("node-1", "get_convergence_state")
            peers = {p["peer"]: p for p in st["peers"]}
            assert peers["node-2"]["synced"]
            assert not peers["node-2"]["backoff_error"]
        finally:
            await cluster.stop()

    asyncio.run(main())
