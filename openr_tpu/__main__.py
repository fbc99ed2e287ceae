"""Process entry point: one real Open/R node.

reference: openr/Main.cpp † — parse config, construct all queues and
modules in dependency order, start servers, install signal handlers,
run until stopped, tear down in reverse order.

    python -m openr_tpu --config node.json [--dataplane netlink|none]

Dataplanes:
  * ``netlink`` — real router mode: kernel interfaces feed LinkMonitor
    through the native netlink event source, and routes are programmed
    into the kernel FIB via the native library (requires CAP_NET_ADMIN
    and `make -C native`).
  * ``none`` (default) — control-plane overlay mode: interfaces are the
    static point-to-point UDP links from `udp_interfaces` in the config
    and the FIB handler is the in-memory mock (useful for multi-host
    control-plane deployments and development).

KvStore peering and the ctrl API listen on `kvstore_port` / `ctrl_port`
at `endpoint_host`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import signal
import sys

from openr_tpu.config import Config
from openr_tpu.fib import MockFibHandler
from openr_tpu.kvstore import TcpKvTransport
from openr_tpu.node import OpenrNode
from openr_tpu.rpc import RpcServer
from openr_tpu.spark.io import UdpIoProvider
from openr_tpu.types.events import InterfaceEvent, InterfaceInfo

log = logging.getLogger("openr_tpu.main")


def _write_ready(path: str, payload: dict) -> None:
    """Atomic readiness handshake: the supervisor polls for this file,
    so a partially written JSON must never be observable. The persist
    plane's atomic-write discipline (fsync-temp → rename → fsync-
    parent-dir) is the one durability implementation in the tree."""
    from openr_tpu.persist import atomic_write_bytes

    atomic_write_bytes(path, json.dumps(payload).encode())


async def run_node(
    config: Config,
    dataplane: str,
    store_path: str | None,
    ready_file: str | None = None,
    persist_dir: str | None = None,
):
    io = UdpIoProvider()
    # bound ports per interface: with local_port=0 in the config every
    # interface binds an ephemeral port (co-hosted processes can never
    # collide) and the readiness handshake tells the supervisor where
    # each one landed; peers may be wired later over ctrl set_udp_peer
    udp_ports: dict[str, int] = {}
    for u in config.node.udp_interfaces:
        peer = (
            (u.peer_host, u.peer_port) if u.peer_port else None
        )
        udp_ports[u.if_name] = await io.add_interface(
            u.if_name, u.local_port, peer
        )

    persist = None
    if persist_dir is not None:
        from openr_tpu.persist import PersistPlane

        # constructed before the node so the mock dataplane below can
        # restore its surviving routes from the same journal; the node
        # attaches its Counters registry on construction
        persist = PersistPlane(persist_dir)

    if dataplane == "netlink":
        from openr_tpu.platform import NetlinkFibService

        fib_handler = NetlinkFibService()
    elif persist is not None:
        # a real kernel FIB outlives the daemon; the durable mock is
        # what makes SIGKILL→restart a warm boot instead of a silent
        # cold boot (persist/dataplane.py)
        from openr_tpu.persist.dataplane import DurableMockFibHandler

        fib_handler = DurableMockFibHandler(persist)
    else:
        fib_handler = MockFibHandler()

    host = config.node.endpoint_host
    # KvStore peering listener FIRST: its bound port (ephemeral-capable)
    # is what Spark advertises to neighbors (reference: the thrift
    # server carrying KvStore peer sessions †)
    from openr_tpu.rpc.tls import client_ssl_context, server_ssl_context

    kv_rpc = RpcServer(f"{config.node_name}.kv")
    kv_port = await kv_rpc.start(
        host, config.node.kvstore_port,
        ssl=server_ssl_context(config.node.tls),
    )
    log.info(
        "kvstore peering on %s:%d%s", host, kv_port,
        " (tls)" if config.node.tls.enabled else "",
    )

    node = OpenrNode(
        config,
        io,
        TcpKvTransport(ssl=client_ssl_context(config.node.tls)),
        fib_handler=fib_handler,
        kvstore_port=kv_port,
        endpoint_host=host,
        enable_ctrl=True,
        ctrl_port=config.node.ctrl_port,
        store_path=store_path,
        persist=persist,
    )
    node.kvstore.register_rpc(kv_rpc)
    # wire-level byte accounting (rpc.bytes_tx/rx): the listener exists
    # before the node's Counters do, so attach post-construction —
    # connections only arrive after start()
    kv_rpc.counters = node.counters

    iface_src = None
    if dataplane == "netlink":
        from openr_tpu.nl.interface_source import NetlinkInterfaceSource

        iface_src = NetlinkInterfaceSource(
            node.name, node.interface_events, counters=node.counters
        )

    await node.start()
    if iface_src is not None:
        await iface_src.start()
    elif config.node.udp_interfaces:
        node.interface_events.push(
            InterfaceEvent(
                interfaces=[
                    InterfaceInfo(name=u.if_name, is_up=True)
                    for u in config.node.udp_interfaces
                ]
            )
        )
    log.info(
        "node %s up (ctrl %s:%d, dataplane=%s)",
        node.name, host, node.ctrl.port if node.ctrl else 0, dataplane,
    )

    # readiness handshake (supervisor contract, docs/Emulator.md): every
    # listener is bound and the node is serving ctrl — report where.
    # The stdout line is the human/pipe channel; the ready file is the
    # machine channel the multi-process supervisor polls.
    ready = {
        "node": node.name,
        "pid": os.getpid(),
        "ctrl_port": node.ctrl.port if node.ctrl else None,
        "kvstore_port": kv_port,
        "udp_ports": udp_ports,
    }
    print(f"OPENR_READY {json.dumps(ready, sort_keys=True)}", flush=True)
    if ready_file:
        _write_ready(ready_file, ready)

    stop_ev = asyncio.Event()
    loop = asyncio.get_event_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop_ev.set)
    await stop_ev.wait()

    log.info("shutting down")
    if iface_src is not None:
        await iface_src.stop()
    await node.stop()
    await kv_rpc.stop()
    if hasattr(fib_handler, "close"):
        fib_handler.close()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="openr_tpu")
    ap.add_argument("--config", required=True, help="node config JSON path")
    ap.add_argument(
        "--dataplane", choices=("none", "netlink"), default="none"
    )
    ap.add_argument(
        "--store-path", default=None,
        help="PersistentStore snapshot path (default: no persistence)",
    )
    ap.add_argument(
        "--persist-dir", default=None,
        help="crash-consistent journal directory (docs/Persist.md):"
        " originated keys, redistribution books and the programmed FIB"
        " survive SIGKILL and warm-boot on restart (default: off)",
    )
    ap.add_argument("--log-level", default="INFO")
    ap.add_argument(
        "--ready-file", default=None,
        help="write a JSON readiness handshake (node, pid, bound ctrl/"
        "kvstore/udp ports) here once all listeners are up — the"
        " multi-process supervisor's port-discovery channel; on a bind"
        " failure the file carries {'error': ...} instead so the"
        " supervisor fails fast rather than hanging on wait_initialized",
    )
    ap.add_argument(
        "--jax-platform", default=None,
        help="force the jax backend (e.g. 'cpu') for this process,"
        " overriding JAX_PLATFORMS; a chip belongs to one process, so"
        " every node but the one that owns it runs with 'cpu'",
    )
    args = ap.parse_args(argv)
    if args.jax_platform:
        import jax

        jax.config.update("jax_platforms", args.jax_platform)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname).1s %(name)s: %(message)s",
    )
    config = Config.from_file(args.config)
    try:
        asyncio.run(
            run_node(
                config, args.dataplane, args.store_path,
                ready_file=args.ready_file,
                persist_dir=args.persist_dir,
            )
        )
    except OSError as e:
        # bind collision / unroutable endpoint_host: a co-hosted process
        # already owns a pinned port. Fail FAST and loudly — the old
        # behavior (module task dies, process lingers, the supervisor's
        # wait_initialized hangs forever) is exactly what the handshake
        # exists to prevent
        msg = (
            f"FATAL: node {config.node_name!r} could not bind its"
            f" listeners: {e} — pinned ctrl_port/kvstore_port/local_port"
            " values collide with another process; use port 0 for"
            " ephemeral allocation"
        )
        print(msg, file=sys.stderr, flush=True)
        if args.ready_file:
            _write_ready(
                args.ready_file,
                {"node": config.node_name, "error": str(e)},
            )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
