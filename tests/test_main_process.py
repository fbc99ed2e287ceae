"""Process-level integration: two real `python -m openr_tpu` daemons on
localhost (UDP point-to-point Spark link, TCP KvStore peering, ctrl
API), driven externally exactly as an operator would (reference
analogue: the reference's end-to-end OpenrTest, but across real
processes and sockets)."""

import asyncio
import json
import socket
import sys

import pytest


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
    return {
        "node_name": name,
        "ctrl_port": ctrl,
        "kvstore_port": kv,
        "endpoint_host": "127.0.0.1",
        "spark": {
            "hello_time_ms": 200,
            "fastinit_hello_time_ms": 50,
            "handshake_time_ms": 50,
            "keepalive_time_ms": 100,
            "hold_time_ms": 1000,
            "graceful_restart_time_ms": 3000,
        },
        "kvstore": {"initial_sync_grace_s": 0.5},
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


async def _wait_cli(port, args, want, timeout=30.0, interval=0.5):
    """Poll a breeze command until `want(stdout)` is true."""
    loop = asyncio.get_event_loop()
    deadline = loop.time() + timeout
    last = ""
    while loop.time() < deadline:
        p = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "openr_tpu.cli", "--port", str(port),
            *args,
            stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE,
        )
        try:
            out, _err = await asyncio.wait_for(p.communicate(), 20.0)
        except asyncio.TimeoutError:
            # a breeze call that neither answers nor fails is one more
            # unsatisfied poll, not a wait without end
            p.kill()
            await p.wait()
            last = "<cli did not return within 20 s>"
            continue
        last = out.decode()
        if p.returncode == 0 and want(last):
            return last
        await asyncio.sleep(interval)
    raise AssertionError(f"cli {args} never satisfied; last:\n{last}")


@pytest.mark.timeout(120)
def test_two_process_convergence(tmp_path):
    async def main():
        ctrl_a, ctrl_b, kv_a, kv_b, udp_a, udp_b = _free_ports(6)
        cfg_a = tmp_path / "a.json"
        cfg_b = tmp_path / "b.json"
        await asyncio.to_thread(cfg_a.write_text, json.dumps(_node_cfg(
            "proc-a", ctrl_a, kv_a, udp_a, udp_b, "10.99.0.1/32")))
        await asyncio.to_thread(cfg_b.write_text, json.dumps(_node_cfg(
            "proc-b", ctrl_b, kv_b, udp_b, udp_a, "10.99.0.2/32")))

        procs = []
        logs = []
        try:
            for cfg in (cfg_a, cfg_b):
                # log to files, not PIPEs: an unread full pipe buffer
                # would deadlock a chatty/failing daemon
                lf = await asyncio.to_thread(  # noqa: SIM115
                    open, str(cfg) + ".log", "wb"
                )
                logs.append(lf)
                procs.append(
                    await asyncio.create_subprocess_exec(
                        sys.executable, "-m", "openr_tpu",
                        "--config", str(cfg), "--log-level", "WARNING",
                        "--jax-platform", "cpu",
                        stdout=lf, stderr=lf,
                    )
                )
            # each node learns the other's loopback through the full
            # pipeline: Spark UDP → LinkMonitor → KvStore TCP sync →
            # Decision → Fib (mock dataplane)
            await _wait_cli(
                ctrl_a, ["fib", "routes"],
                lambda out: "10.99.0.2/32" in out,
            )
            await _wait_cli(
                ctrl_b, ["fib", "routes"],
                lambda out: "10.99.0.1/32" in out,
            )
            # operator health check passes end-to-end
            out = await _wait_cli(
                ctrl_a, ["validate"], lambda o: "all checks passed" in o
            )
            assert "[PASS] spark.neighbors_advertised" in out
        finally:
            for p in procs:
                if p.returncode is None:
                    p.terminate()
            for p in procs:
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except asyncio.TimeoutError:
                    p.kill()
            for lf in logs:
                lf.close()

    asyncio.run(main())
