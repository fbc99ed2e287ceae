"""Multi-process cluster harness: real processes, real sockets, real
signals.

The in-process emulator (emulator/cluster.py) co-schedules N OpenrNodes
on one asyncio loop — crash_node is a cancelled task, partitions are
dict flips, and one loop serializes every flood fan-out. This module is
the other half of the robustness story: a supervisor that spawns each
node as ``python -m openr_tpu`` (its own interpreter, its own loop),
wired over the seams that already abstract the process boundary —

  * Spark neighbor discovery over **real UDP sockets**
    (``spark/io.py`` ``UdpIoProvider``; one ephemeral localhost port
    per interface),
  * KvStore flooding/full-sync over **real TCP** (``kvstore/
    transport.py`` ``TcpKvTransport`` + the negotiated binary codec),
  * all observation and chaos control over **ctrl RPC**
    (``ctrl/server.py`` — including the harness endpoints:
    get_convergence_state / get_kvstore_digest / check_fib_oracle /
    chaos_set_drop / set_udp_peer / work_ledger_control).

Faults are REAL: ``crash_node`` is SIGKILL (or a graceful-restart
announcement + SIGTERM), ``hang_node`` is SIGSTOP, partitions are
socket-level drop rules installed in the target processes' io
providers, and ``restart_node`` is a genuine re-exec that re-syncs the
LSDB from peers. The method surface mirrors ``Cluster`` closely enough
that ``chaos.run_schedule`` drives either (link/partition methods are
coroutines here; the dispatcher awaits whatever it gets back).

Port allocation is collision-free by construction: every listener and
UDP socket in a generated config binds port 0, the node process reports
its bound ports through the ``--ready-file`` readiness handshake
(openr_tpu/__main__.py), and the supervisor wires each link's two
endpoints together afterwards via ctrl ``set_udp_peer`` —
``UdpIoProvider.send`` no-ops until its peer is set, and Spark hellos
are periodic, so discovery starts by itself once both ends are wired.

See docs/Emulator.md "Multi-process clusters" for the lifecycle and
fault matrix.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field, replace

from openr_tpu.config import Config, NodeConfig, OriginatedPrefix
from openr_tpu.config.config import UdpInterfaceConfig
from openr_tpu.emulator.cluster import LinkSpec, loopback_of, scaled_spark
from openr_tpu.rpc import RpcClient, RpcError

log = logging.getLogger(__name__)

#: readiness-handshake patience: N interpreters starting on (possibly)
#: one core serialize their imports; scaled by fleet size at wait time
READY_BASE_TIMEOUT_S = 30.0
#: how long a SIGTERMed child may take over its orderly shutdown before
#: it is killed
_EXIT_GRACE_S = 10.0

_LOG_TAIL = 30  # lines of a dead node's log quoted in errors


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class ProcNode:
    """Supervisor-side handle for one spawned node process."""

    name: str
    config_path: str
    log_path: str
    ready_path: str
    proc: subprocess.Popen | None = None
    ready: dict = field(default_factory=dict)  # the handshake payload
    ctrl: RpcClient | None = None
    interfaces: dict[str, str] = field(default_factory=dict)  # if -> peer
    #: journal directory (docs/Persist.md); survives crash/restart so a
    #: re-exec is a WARM boot — originated keys, redistribution books
    #: and the programmed FIB come back from disk, not from peers
    persist_dir: str | None = None

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    @property
    def pid(self) -> int | None:
        return self.proc.pid if self.proc is not None else None

    @property
    def ctrl_port(self) -> int | None:
        return self.ready.get("ctrl_port")

    def log_tail(self, n: int = _LOG_TAIL) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "<no log>"


class ProcCluster:
    """N real node processes + the chaos/observation control plane.

    Mirrors emulator.Cluster's surface (nodes / crashed / links /
    fail_link / heal_link / crash_node / restart_node / partition /
    heal_partition / converged / wait_converged / make_storm /
    fleet_counters) so the PR 3/4/16 chaos + soak machinery runs
    unchanged — with the difference that every fault crosses a real
    process boundary.
    """

    def __init__(
        self,
        links: list[LinkSpec],
        workdir: str,
        python: str | None = None,
        prefixes_per_node: int = 0,
        host: str = "127.0.0.1",
        spark_scale_cap: float = 20.0,
        persist: bool = True,
        spark_overrides: dict | None = None,
    ):
        self.links = links
        self.workdir = workdir
        self.python = python or sys.executable
        self.host = host
        self.nodes: dict[str, ProcNode] = {}
        self.crashed: dict[str, ProcNode] = {}
        self.hung: dict[str, ProcNode] = {}
        self._partitioned: list[LinkSpec] = []
        names = sorted({ls.a for ls in links} | {ls.b for ls in links})
        self.names = names
        os.makedirs(workdir, exist_ok=True)
        n = len(names)
        # Host-oversubscription scaling. The in-proc emulator's
        # scaled_spark covers coroutine crowding on ONE loop; here every
        # node is an interpreter PROCESS contending for the host's
        # cores, and each process stalls its own event loop for the
        # duration of its solver + FIB work (O(prefixes)). A hold timer
        # must survive the worst such stall times the scheduling
        # multiplier, or CPU contention masquerades as neighbor loss
        # and the fleet churns itself forever (observed: 8 procs on 1
        # core, 100 prefixes each — 573 ms full rebuilds vs a 400 ms
        # hold). Real routers run multi-second holds for the same
        # reason.
        cpu = os.cpu_count() or 1
        factor = max(
            1.0,
            (n / cpu) / 4.0,  # >4 interpreters per core: stretch
            n * (1 + prefixes_per_node) / 4000.0,  # solver stall term
        )
        factor = min(factor, spark_scale_cap)
        base = scaled_spark(n)
        spark_cfg = replace(
            base,
            hello_time_ms=int(base.hello_time_ms * factor),
            fastinit_hello_time_ms=int(
                base.fastinit_hello_time_ms * factor
            ),
            handshake_time_ms=int(base.handshake_time_ms * factor),
            keepalive_time_ms=int(base.keepalive_time_ms * factor),
            hold_time_ms=int(base.hold_time_ms * factor),
            graceful_restart_time_ms=int(
                base.graceful_restart_time_ms * factor
            ),
        )
        if spark_overrides:
            # crash-recovery tests pin hold/GR above the worst re-exec
            # time: a warm boot is only "hitless" if the survivors'
            # hold timers outlive the victim's restart window
            spark_cfg = replace(spark_cfg, **spark_overrides)
        self.spark_factor = round(factor, 2)
        debounce = (10, max(60, int(60 * factor)))
        for i, name in enumerate(names):
            ifaces = {}
            for ls in links:
                if ls.a == name:
                    ifaces[ls.a_if] = ls.b
                elif ls.b == name:
                    ifaces[ls.b_if] = ls.a
            originated = [OriginatedPrefix(prefix=loopback_of(i))]
            for p in range(prefixes_per_node):
                # deterministic per-node prefix block out of 100.64/10
                originated.append(OriginatedPrefix(
                    prefix=f"100.{64 + (i >> 8)}.{i & 0xFF}.{p % 256}/32"
                    if p < 256 else
                    f"100.{96 + (p >> 8)}.{i & 0xFF}.{p & 0xFF}/32"
                ))
            ncfg = NodeConfig(
                node_name=name,
                spark=spark_cfg,
                originated_prefixes=tuple(originated),
                # everything ephemeral: the readiness handshake is the
                # only source of truth for where this node listens
                ctrl_port=0,
                kvstore_port=0,
                endpoint_host=host,
                udp_interfaces=tuple(
                    # local_port=0 (bind ephemeral), peer_port=0 (defer
                    # wiring to the supervisor's set_udp_peer pass)
                    UdpInterfaceConfig(
                        if_name=ifn, local_port=0,
                        peer_host=host, peer_port=0,
                    )
                    for ifn in sorted(ifaces)
                ),
            )
            ncfg = replace(
                ncfg,
                decision=replace(
                    ncfg.decision,
                    # real fleets of single-node interpreters must not
                    # each warm a jax jit cache: the CPU oracle is the
                    # right per-process solver at emulation scale
                    use_tpu_solver=False,
                    debounce_min_ms=debounce[0],
                    debounce_max_ms=debounce[1],
                ),
            )
            cfg_path = os.path.join(workdir, f"{name}.json")
            with open(cfg_path, "w") as f:
                f.write(Config(ncfg).to_json())
            self.nodes[name] = ProcNode(
                name=name,
                config_path=cfg_path,
                log_path=os.path.join(workdir, f"{name}.log"),
                ready_path=os.path.join(workdir, f"{name}.ready.json"),
                interfaces=ifaces,
                # persistence on by default: a ProcCluster restart is a
                # warm boot, which is what the crash-recovery invariants
                # (proc_invariants.persist_parity) exercise
                persist_dir=(
                    os.path.join(workdir, f"{name}.persist")
                    if persist else None
                ),
            )

    @staticmethod
    def from_edges(
        edges, workdir: str, prefixes_per_node: int = 0, **kw
    ) -> "ProcCluster":
        links = [
            e if isinstance(e, LinkSpec) else LinkSpec(a=e[0], b=e[1])
            for e in edges
        ]
        return ProcCluster(
            links, workdir, prefixes_per_node=prefixes_per_node, **kw
        )

    # ------------------------------------------------------------ lifecycle

    def _spawn(self, pn: ProcNode) -> None:
        try:
            os.unlink(pn.ready_path)
        except OSError:
            pass
        logf = open(pn.log_path, "a")
        env = dict(os.environ)
        # the child runs with cwd=workdir (its logs/stores land there),
        # so when the package is imported from a source tree rather
        # than installed, hand the tree to the child explicitly
        import openr_tpu

        pkg_root = os.path.dirname(
            os.path.dirname(os.path.abspath(openr_tpu.__file__))
        )
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (pkg_root, env.get("PYTHONPATH")) if p
        )
        # a chip belongs to one process: a child that reached for it
        # would fail or hang behind whoever holds it, so every node
        # process is pinned to the CPU backend — and with
        # use_tpu_solver=False it never imports jax at all (the import
        # is lazy); the pin covers the odd path (compile ledger) that
        # does. Multi-process harnesses therefore measure the host path
        # only, never the device.
        env["JAX_PLATFORMS"] = "cpu"
        pn.proc = subprocess.Popen(
            [
                self.python, "-m", "openr_tpu",
                "--config", pn.config_path,
                "--ready-file", pn.ready_path,
                "--log-level", "WARNING",
                *(
                    ["--persist-dir", pn.persist_dir]
                    if pn.persist_dir else []
                ),
            ],
            stdout=logf,
            stderr=subprocess.STDOUT,
            env=env,
            cwd=self.workdir,
        )
        logf.close()  # the child owns the fd now

    async def _wait_ready(self, pns: list[ProcNode]) -> None:
        """Poll the ready files; fail FAST on a dead process or an
        {'error': ...} handshake instead of hanging on convergence."""
        timeout = READY_BASE_TIMEOUT_S + 1.5 * len(self.names)
        deadline = time.monotonic() + timeout
        pending = list(pns)
        while pending:
            still = []
            for pn in pending:
                if os.path.exists(pn.ready_path):
                    ready = await asyncio.to_thread(_read_json, pn.ready_path)
                    if "error" in ready:
                        raise RuntimeError(
                            f"node {pn.name} failed to bind: "
                            f"{ready['error']}\n--- {pn.name} log tail "
                            f"---\n{pn.log_tail()}"
                        )
                    pn.ready = ready
                    continue
                if not pn.alive:
                    raise RuntimeError(
                        f"node {pn.name} exited rc={pn.proc.returncode} "
                        f"before reporting ready\n--- {pn.name} log tail"
                        f" ---\n{pn.log_tail()}"
                    )
                still.append(pn)
            pending = still
            if pending and time.monotonic() > deadline:
                raise RuntimeError(
                    f"{len(pending)} node(s) not ready after "
                    f"{timeout:.0f}s: "
                    f"{sorted(pn.name for pn in pending)[:8]}"
                )
            if pending:
                await asyncio.sleep(0.1)

    async def _ctrl(self, pn: ProcNode) -> RpcClient:
        """Pooled ctrl client; (re)connects lazily — a node that was
        killed and restarted comes back on a new ctrl port, so the
        stale client is dropped whenever the connection is gone."""
        if pn.ctrl is not None and pn.ctrl.connected:
            return pn.ctrl
        if pn.ctrl is not None:
            await pn.ctrl.close()
        pn.ctrl = RpcClient(self.host, pn.ready["ctrl_port"])
        await pn.ctrl.connect()
        return pn.ctrl

    async def call(
        self, name: str, method: str, params: dict | None = None,
        timeout: float = 30.0,
    ):
        pn = self.nodes.get(name) or self.crashed.get(name)
        if pn is None:
            raise KeyError(name)
        c = await self._ctrl(pn)
        return await c.call(method, params or {}, timeout=timeout)

    async def _wire_links(self, names: set[str] | None = None) -> None:
        """Point each link endpoint's UDP socket at its neighbor's
        bound port. With `names`, only links touching those nodes are
        (re)wired — the restart path, where the restarted node AND each
        neighbor's facing interface both need the fresh ports."""
        for ls in self.links:
            if names is not None and not ({ls.a, ls.b} & names):
                continue
            a, b = self.nodes.get(ls.a), self.nodes.get(ls.b)
            if a is None or b is None:
                continue  # endpoint crashed; restart re-wires it
            await self.call(ls.a, "set_udp_peer", {
                "if_name": ls.a_if, "host": self.host,
                "port": b.ready["udp_ports"][ls.b_if],
            })
            await self.call(ls.b, "set_udp_peer", {
                "if_name": ls.b_if, "host": self.host,
                "port": a.ready["udp_ports"][ls.a_if],
            })

    async def start(self) -> None:
        for pn in self.nodes.values():
            self._spawn(pn)
        await self._wait_ready(list(self.nodes.values()))
        await self._wire_links()

    async def stop(self) -> None:
        for pn in list(self.nodes.values()) + list(self.crashed.values()):
            if pn.ctrl is not None:
                try:
                    await pn.ctrl.close()
                except RpcError:
                    pass
                pn.ctrl = None
            if pn.alive:
                pn.proc.send_signal(signal.SIGCONT)  # un-hang first
                pn.proc.terminate()
        deadline = time.monotonic() + _EXIT_GRACE_S
        for pn in list(self.nodes.values()) + list(self.crashed.values()):
            if pn.proc is not None:
                await self._reap(pn, deadline)

    @staticmethod
    async def _reap(pn: ProcNode, deadline: float) -> None:
        """Collect a signalled child. One that is still there at
        `deadline` (a SIGTERM it never acts on) is killed. Polled, not
        `to_thread(proc.wait)`: a thread waiting for a child that does
        not die outlives every timeout, and `asyncio.run` waits for it
        on the way out."""
        while pn.alive and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        if pn.alive:
            pn.proc.kill()
        pn.proc.wait(timeout=_EXIT_GRACE_S)  # killed or gone: at once

    def endpoints(self) -> list[str]:
        """Live ctrl endpoints, `breeze --endpoints` format."""
        return [
            f"{self.host}:{pn.ready['ctrl_port']}"
            for pn in self.nodes.values()
            if pn.ready.get("ctrl_port")
        ]

    # ----------------------------------------------------------- assertions

    async def converged(self) -> bool:
        """Every live process initialized with a route to every other
        live node's loopback (same definition as Cluster.converged,
        answered over ctrl)."""
        n_remote = len(self.nodes) - 1
        for pn in self.nodes.values():
            try:
                st = await self.call(
                    pn.name, "get_convergence_state", timeout=10.0
                )
            except (RpcError, OSError):
                return False
            if not st["initialized"]:
                return False
            if st["fib"]["programmed_unicast"] < n_remote:
                return False
        return True

    async def wait_converged(self, timeout: float = 120.0) -> None:
        t0 = time.monotonic()
        while not await self.converged():
            if time.monotonic() - t0 > timeout:
                detail = {}
                for pn in self.nodes.values():
                    try:
                        st = await self.call(
                            pn.name, "get_convergence_state", timeout=5.0
                        )
                        detail[pn.name] = (
                            st["initialized"],
                            st["fib"]["programmed_unicast"],
                        )
                    except (RpcError, OSError):
                        detail[pn.name] = (
                            "alive" if pn.alive else "dead", None
                        )
                raise TimeoutError(
                    f"proc cluster did not converge: {detail}"
                )
            await asyncio.sleep(0.25)

    async def fleet_counters(self, prefix: str = "") -> dict:
        from openr_tpu.monitor.fleet import aggregate_counters

        snaps = {}
        for pn in self.nodes.values():
            try:
                snaps[pn.name] = await self.call(
                    pn.name, "get_counters", {"prefix": prefix}
                )
            except (RpcError, OSError):
                continue
        return aggregate_counters(snaps, prefix=prefix)

    # ------------------------------------------------------------- persist

    async def get_persist_status(self, name: str) -> dict:
        """Journal health + per-book digests over ctrl — the byte-parity
        token proc_invariants.persist_parity snapshots BEFORE a crash
        and compares against the restarted incarnation's recovery."""
        return await self.call(name, "get_persist_status")

    async def inject_disk_fault(self, name: str, kind: str, **params):
        """Arm a one-shot disk fault (torn / corrupt / enospc /
        crash_between_rename / slow_fsync) in the target PROCESS's
        persist plane — the chaos machinery's durable-storage seam.
        The fault fires at the next matching journal edge."""
        return await self.call(
            name, "persist_control",
            {"op": "inject", "kind": kind, "params": params},
        )

    # -------------------------------------------------------------- control

    def _links_between(self, a: str, b: str) -> list[LinkSpec]:
        found = [ls for ls in self.links if {ls.a, ls.b} == {a, b}]
        if not found:
            raise ValueError(f"no link between {a!r} and {b!r}")
        return found

    async def _set_drop(self, node: str, if_names: list[str], op: str):
        pn = self.nodes.get(node)
        if pn is None or not pn.alive:
            return  # crashed/hung endpoint: nothing to install
        try:
            await self.call(node, "chaos_set_drop", {
                "if_names": if_names, "op": op,
            })
        except (RpcError, OSError):
            # a process dying mid-partition is chaos working as
            # intended; the drop rule dies with the process
            log.debug("chaos_set_drop on %s failed (process gone?)", node)

    async def fail_link(self, a: str, b: str) -> None:
        """Socket-level silent loss: both endpoints' UDP interfaces for
        the (a, b) link drop tx AND rx, so the adjacency dies by Spark
        hold expiry — and the KvStore TCP session follows when
        LinkMonitor withdraws the peer. No process is told anything."""
        for ls in self._links_between(a, b):
            await self._set_drop(ls.a, [ls.a_if], "add")
            await self._set_drop(ls.b, [ls.b_if], "add")

    async def heal_link(self, a: str, b: str) -> None:
        """Remove the drop rules; periodic hellos resume on their own
        (the interfaces never went down, only their packets did)."""
        for ls in self._links_between(a, b):
            await self._set_drop(ls.a, [ls.a_if], "remove")
            await self._set_drop(ls.b, [ls.b_if], "remove")

    # ------------------------------------------------------- crash archetypes

    async def crash_node(self, name: str, graceful: bool = False) -> None:
        """Hard crash = SIGKILL (nothing flushed, sockets RST on next
        use — peers' in-flight syncs surface transport errors and land
        in backoff). Graceful = announce Spark GR over ctrl, then
        SIGTERM for the orderly shutdown path."""
        pn = self.nodes.pop(name)  # KeyError: unknown or already crashed
        # register under crashed FIRST: call() resolves through both
        # maps, and the graceful path still needs one ctrl round trip
        self.crashed[name] = pn
        if graceful and pn.alive:
            try:
                await self.call(name, "spark_announce_restart", timeout=5.0)
            except (RpcError, OSError):
                pass  # already dying — a hard crash then
        if pn.ctrl is not None:
            try:
                await pn.ctrl.close()
            except RpcError:
                pass
            pn.ctrl = None
        if pn.alive:
            pn.proc.send_signal(
                signal.SIGTERM if graceful else signal.SIGKILL
            )
            await self._reap(pn, time.monotonic() + _EXIT_GRACE_S)

    async def restart_node(self, name: str) -> None:
        """Real re-exec from the same config: fresh interpreter, fresh
        ephemeral ports. The readiness handshake reports the new ports
        and the re-wire pass updates BOTH the restarted node's
        interfaces and every neighbor's facing interface; neighbors
        re-learn the new kvstore port from the Spark handshake
        (KvStore re-peers when a known neighbor's endpoint moves)."""
        pn = self.crashed.pop(name)
        pn.ready = {}
        self._spawn(pn)
        self.nodes[name] = pn
        await self._wait_ready([pn])
        await self._wire_links(names={name})

    async def hang_node(self, name: str) -> None:
        """SIGSTOP: the process exists but schedules nothing — TCP
        stays ESTABLISHED while hellos stop, the fault mode an asyncio
        cancel can't fake. Neighbors must detect via hold expiry."""
        pn = self.nodes.pop(name)
        pn.proc.send_signal(signal.SIGSTOP)
        self.hung[name] = pn

    async def resume_node(self, name: str) -> None:
        """SIGCONT a hung process; its timers fire late, its neighbors
        have long since withdrawn it, and it must re-converge."""
        pn = self.hung.pop(name)
        pn.proc.send_signal(signal.SIGCONT)
        self.nodes[name] = pn

    # ------------------------------------------------------------ partition

    async def partition(self, groups) -> None:
        """Cross-group links go down at the socket layer on both ends
        (same membership semantics as Cluster.partition; composes)."""
        all_names = set(self.nodes) | set(self.crashed) | set(self.hung)
        membership: dict[str, int] = {}
        for gi, group in enumerate(groups):
            for n in group:
                if n not in all_names:
                    raise ValueError(
                        f"partition group names unknown node {n!r}"
                    )
                membership[n] = gi
        for ls in self.links:
            ga, gb = membership.get(ls.a), membership.get(ls.b)
            if ga == gb and ga is not None:
                continue
            if ga is None and gb is None:
                continue
            await self._set_drop(ls.a, [ls.a_if], "add")
            await self._set_drop(ls.b, [ls.b_if], "add")
            self._partitioned.append(ls)

    async def heal_partition(self) -> None:
        healed, self._partitioned = self._partitioned, []
        for ls in healed:
            await self._set_drop(ls.a, [ls.a_if], "remove")
            await self._set_drop(ls.b, [ls.b_if], "remove")

    # ----------------------------------------------------- chaos: flap storm

    def make_storm(
        self,
        plan,
        *,
        duration_s: float = 2.0,
        n_flaps: int = 0,
        n_crashes: int = 0,
        n_partitions: int = 0,
        heal_after_s: float = 0.6,
        n_disk_faults: int = 0,
    ):
        """Deterministic fault schedule over this cluster's real link/
        node sets — same generator as the in-process emulator, so a
        seed replays identically on either harness. Disk-fault crashes
        (`n_disk_faults`) only bite here: the armed journal fault lands
        in a real process whose restart warm-boots through the damage."""
        return plan.build_storm(
            [(ls.a, ls.b) for ls in self.links],
            sorted(set(self.nodes) | set(self.crashed)),
            duration_s=duration_s,
            n_flaps=n_flaps,
            n_crashes=n_crashes,
            n_partitions=n_partitions,
            heal_after_s=heal_after_s,
            n_disk_faults=n_disk_faults,
        )
