"""Spark + LinkMonitor tests.

reference analogues: openr/spark/tests/SparkTest.cpp † (MockIoProvider
wiring N Spark instances with latency/partitions; FSM, hold timers, GR)
and openr/link-monitor/tests/LinkMonitorTest.cpp † (adjacency
advertisement, flap damping, overload)."""

import asyncio

import pytest

from openr_tpu.common.constants import adj_key
from openr_tpu.config import Config, NodeConfig, SparkConfig
from openr_tpu.kvstore import InProcKvTransport, KvStore, KvStoreClient
from openr_tpu.linkmonitor import LinkMonitor
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters
from openr_tpu.spark import MockIoHub, Spark, SparkNeighborState
from openr_tpu.types.events import (
    InterfaceEvent,
    InterfaceInfo,
    NeighborEventType,
)
from openr_tpu.types.serde import from_wire
from openr_tpu.types.topology import AdjacencyDatabase


def run(coro):
    # asyncio.run: closes the loop, cancels leftovers, shuts down
    # async generators — the teardown hygiene the sanitizer checks
    return asyncio.run(coro)


FAST = SparkConfig(
    hello_time_ms=60,
    fastinit_hello_time_ms=20,
    handshake_time_ms=20,
    keepalive_time_ms=40,
    hold_time_ms=200,
    graceful_restart_time_ms=600,
)


def mk_spark(hub, name, kvstore_port=0):
    cfg = Config(NodeConfig(node_name=name, spark=FAST))
    q = ReplicateQueue(name=f"{name}.nbr")
    sp = Spark(
        cfg,
        hub.io_for(name),
        q,
        kvstore_port=kvstore_port,
        counters=Counters(),
    )
    return sp, q


async def settle(cond, timeout=3.0):
    t0 = asyncio.get_event_loop().time()
    while not cond():
        if asyncio.get_event_loop().time() - t0 > timeout:
            return False
        await asyncio.sleep(0.01)
    return True


def test_two_node_discovery_and_hold_timer():
    async def main():
        hub = MockIoHub()
        sa, qa = mk_spark(hub, "a", kvstore_port=1111)
        sb, qb = mk_spark(hub, "b", kvstore_port=2222)
        ra, rb = qa.get_reader(), qb.get_reader()
        hub.link("a", "if-ab", "b", "if-ba", latency_ms=1)
        await sa.start()
        await sb.start()
        sa.add_interface("if-ab")
        sb.add_interface("if-ba")

        ok = await settle(
            lambda: sa.neighbors.get(("if-ab", "b")) is not None
            and sa.neighbors[("if-ab", "b")].state
            == SparkNeighborState.ESTABLISHED
            and sb.neighbors.get(("if-ba", "a")) is not None
            and sb.neighbors[("if-ba", "a")].state
            == SparkNeighborState.ESTABLISHED
        )
        assert ok, "neighbors did not establish"
        ev = ra.try_get()
        assert ev is not None and ev.type == NeighborEventType.NEIGHBOR_UP
        assert ev.info.node_name == "b"
        assert ev.info.kvstore_port == 2222  # handshake carried endpoint
        assert ev.info.remote_if == "if-ba"

        # partition → hold timer → NEIGHBOR_DOWN on both sides
        hub.set_link("a", "if-ab", up=False)
        ok = await settle(
            lambda: ("if-ab", "b") not in sa.neighbors
            and ("if-ba", "a") not in sb.neighbors,
            timeout=3.0,
        )
        assert ok, "hold timer did not fire"
        downs = []
        while (e := ra.try_get()) is not None:
            downs.append(e.type)
        assert NeighborEventType.NEIGHBOR_DOWN in downs

        # heal → re-establish
        hub.set_link("a", "if-ab", up=True)
        sa.add_interface("if-ab")  # re-fastinit
        ok = await settle(
            lambda: sa.neighbors.get(("if-ab", "b")) is not None
            and sa.neighbors[("if-ab", "b")].state
            == SparkNeighborState.ESTABLISHED
        )
        assert ok, "did not re-establish after heal"
        await sa.stop()
        await sb.stop()

    run(main())


def test_nongraceful_restart_detected_via_heard_map():
    """SIGKILL-style restart with NO graceful announce: the fresh
    instance's hellos don't carry us in their heard map, so the survivor
    must tear the ESTABLISHED adjacency down and re-negotiate — the
    fresh handshake is what carries the NEW kvstore/ctrl endpoints.
    Without the teardown the survivor keeps flooding a dead endpoint
    forever (found by the multi-process harness, docs/Emulator.md)."""

    async def main():
        hub = MockIoHub()
        sa, qa = mk_spark(hub, "a", kvstore_port=1111)
        sb, _ = mk_spark(hub, "b", kvstore_port=2222)
        ra = qa.get_reader()
        hub.link("a", "if-ab", "b", "if-ba", latency_ms=1)
        await sa.start()
        await sb.start()
        sa.add_interface("if-ab")
        sb.add_interface("if-ba")
        ok = await settle(
            lambda: (nb := sa.neighbors.get(("if-ab", "b"))) is not None
            and nb.state == SparkNeighborState.ESTABLISHED
        )
        assert ok, "initial adjacency did not establish"
        while ra.try_get() is not None:
            pass

        # hard-kill b: no announce_restart, inbox dropped (dead
        # incarnation's backlog gone), fresh instance on a NEW endpoint
        await sb.stop()
        hub.drop_node("b")
        sb2, _ = mk_spark(hub, "b", kvstore_port=3333)
        await sb2.start()
        sb2.add_interface("if-ba")

        ok = await settle(
            lambda: sa.counters.get("spark.nongr_restarts_detected") > 0
            and (nb := sa.neighbors.get(("if-ab", "b"))) is not None
            and nb.state == SparkNeighborState.ESTABLISHED
            and nb.kvstore_port == 3333,
            timeout=5.0,
        )
        assert ok, "survivor never re-learned the restarted instance"
        # two valid detection paths: usually the survivor's stale heard
        # entry fast-tracks the fresh FSM to NEGOTIATE and the
        # unsolicited handshake yields NEIGHBOR_RESTARTED; if the fresh
        # instance's empty-heard hello wins the race instead, the
        # heard-map teardown yields NEIGHBOR_DOWN then NEIGHBOR_UP.
        # Either way the LAST up-ish event must carry the NEW endpoint.
        events = []
        while (e := ra.try_get()) is not None:
            events.append(e)
        upish = [
            e
            for e in events
            if e.type
            in (
                NeighborEventType.NEIGHBOR_UP,
                NeighborEventType.NEIGHBOR_RESTARTED,
            )
        ]
        assert upish, f"no re-peer event emitted: {[e.type for e in events]}"
        assert upish[-1].info.kvstore_port == 3333
        await sa.stop()
        await sb2.stop()

    run(main())


def test_lost_handshake_is_asked_for_again():
    """Handshakes are datagrams. While every handshake towards `a` is
    lost, `b` reaches ESTABLISHED on a's request and `a` waits in
    NEGOTIATE: b's hellos keep a's hold timer quiet and b, established,
    sends no request of its own, so nothing but a's own re-send (every
    handshake_time_ms while the neighbor says it hears us) can end the
    half-open adjacency once the loss stops."""
    from openr_tpu.spark.spark import SparkPacket
    from openr_tpu.types.serde import from_wire_auto

    class LossyHub(MockIoHub):
        lose_handshakes_to_a = True
        lost = 0

        def _enqueue(self, lk, dst_node, dst_if, payload, inbox):
            if self.lose_handshakes_to_a and dst_node == "a":
                if from_wire_auto(payload, SparkPacket).handshake is not None:
                    self.lost += 1
                    return
            super()._enqueue(lk, dst_node, dst_if, payload, inbox)

    def state(sp, key):
        nb = sp.neighbors.get(key)
        return None if nb is None else nb.state

    async def main():
        hub = LossyHub()
        sa, _ = mk_spark(hub, "a", kvstore_port=1111)
        sb, _ = mk_spark(hub, "b", kvstore_port=2222)
        hub.link("a", "if-ab", "b", "if-ba", latency_ms=1)
        await sa.start()
        await sb.start()
        sa.add_interface("if-ab")
        sb.add_interface("if-ba")
        ok = await settle(
            lambda: state(sb, ("if-ba", "a")) == SparkNeighborState.ESTABLISHED
            and state(sa, ("if-ab", "b")) == SparkNeighborState.NEGOTIATE
            and hub.lost >= 1
        )
        assert ok, "never reached the half-open state the test is about"
        hub.lose_handshakes_to_a = False
        ok = await settle(
            lambda: state(sa, ("if-ab", "b")) == SparkNeighborState.ESTABLISHED,
            timeout=2.0,
        )
        assert ok, (
            "a stayed in NEGOTIATE after the loss stopped: its one "
            "handshake was never sent again"
        )
        assert sa.neighbors[("if-ab", "b")].kvstore_port == 2222
        assert state(sb, ("if-ba", "a")) == SparkNeighborState.ESTABLISHED
        await sa.stop()
        await sb.stop()

    run(main())


def test_three_node_star():
    """Hub node sees both leaves on separate interfaces."""

    async def main():
        hub = MockIoHub()
        sh, qh = mk_spark(hub, "hub")
        s1, _ = mk_spark(hub, "leaf1")
        s2, _ = mk_spark(hub, "leaf2")
        hub.link("hub", "if-1", "leaf1", "if-h")
        hub.link("hub", "if-2", "leaf2", "if-h")
        for s in (sh, s1, s2):
            await s.start()
        sh.add_interface("if-1")
        sh.add_interface("if-2")
        s1.add_interface("if-h")
        s2.add_interface("if-h")
        ok = await settle(
            lambda: len(
                [
                    n
                    for n in sh.neighbors.values()
                    if n.state == SparkNeighborState.ESTABLISHED
                ]
            )
            == 2
        )
        assert ok, "star did not form"
        for s in (sh, s1, s2):
            await s.stop()

    run(main())


def test_area_negotiation():
    from openr_tpu.config import AreaConfig

    async def main():
        hub = MockIoHub()
        cfg_a = Config(
            NodeConfig(
                node_name="a",
                spark=FAST,
                areas=(
                    AreaConfig(area_id="spine", neighbor_regexes=("b.*",)),
                    AreaConfig(area_id="0", neighbor_regexes=(".*",)),
                ),
            )
        )
        qa = ReplicateQueue()
        ra = qa.get_reader()
        sa = Spark(cfg_a, hub.io_for("a"), qa, counters=Counters())
        sb, _ = mk_spark(hub, "b1")
        hub.link("a", "if-ab", "b1", "if-ba")
        await sa.start()
        await sb.start()
        sa.add_interface("if-ab")
        sb.add_interface("if-ba")
        ok = await settle(lambda: ra.try_get() is not None or len(sa.neighbors) > 0)
        assert ok
        ok = await settle(
            lambda: sa.neighbors.get(("if-ab", "b1")) is not None
            and sa.neighbors[("if-ab", "b1")].state
            == SparkNeighborState.ESTABLISHED
        )
        assert ok
        # a matched "b.*" → offered area "spine"
        assert sa._negotiate_area("b1") == "spine"
        await sa.stop()
        await sb.stop()

    run(main())


def _mk_node(hub, transport, name):
    """Full discovery stack for one node: Spark + KvStore + LinkMonitor."""
    from openr_tpu.config import LinkMonitorConfig

    cfg = Config(NodeConfig(node_name=name, spark=FAST))
    counters = Counters()
    pubq = ReplicateQueue(name=f"{name}.pub")
    nbrq = ReplicateQueue(name=f"{name}.nbr")
    peerq = ReplicateQueue(name=f"{name}.peer")
    ifq = ReplicateQueue(name=f"{name}.if")
    store = KvStore(
        cfg, transport, pubq, peer_events_reader=peerq.get_reader(),
        counters=counters,
    )
    transport.register(name, store)
    client = KvStoreClient(store, name, pubq.get_reader(), counters=counters)
    spark = Spark(cfg, hub.io_for(name), nbrq, counters=counters)
    lm = LinkMonitor(
        cfg,
        spark,
        client,
        nbrq.get_reader(),
        peerq,
        interface_events_reader=ifq.get_reader(),
        counters=counters,
    )
    return dict(
        cfg=cfg, store=store, client=client, spark=spark, lm=lm,
        pubq=pubq, ifq=ifq, counters=counters,
    )


def test_end_to_end_discovery_to_kvstore():
    """The §3.2 call stack: link up → Spark discovery → LinkMonitor
    adjacency → adj: key in KvStore → flooded to the peer."""

    async def main():
        hub = MockIoHub()
        transport = InProcKvTransport()
        a = _mk_node(hub, transport, "a")
        b = _mk_node(hub, transport, "b")
        hub.link("a", "if-ab", "b", "if-ba")
        for n in (a, b):
            for mod in ("store", "client", "spark", "lm"):
                await n[mod].start()
        a["ifq"].push(InterfaceEvent(interfaces=[InterfaceInfo(name="if-ab")]))
        b["ifq"].push(InterfaceEvent(interfaces=[InterfaceInfo(name="if-ba")]))

        # both adj: keys present in BOTH stores (advertised + flooded)
        def converged():
            for st in (a["store"], b["store"]):
                for node in ("a", "b"):
                    v = st.get_key("0", adj_key(node))
                    if v is None:
                        return False
                    db = from_wire(v.value, AdjacencyDatabase)
                    if len(db.adjacencies) != 1:
                        return False
            return True

        ok = await settle(converged, timeout=5.0)
        assert ok, "discovery → adj → kvstore flood did not converge"
        db = from_wire(
            a["store"].get_key("0", adj_key("b")).value, AdjacencyDatabase
        )
        assert db.adjacencies[0].other_node_name == "a"
        assert db.adjacencies[0].if_name == "if-ba"
        assert db.adjacencies[0].other_if_name == "if-ab"

        # kill the link: adjacency withdrawn everywhere
        hub.set_link("a", "if-ab", up=False)

        def withdrawn():
            va = a["store"].get_key("0", adj_key("a"))
            vb = b["store"].get_key("0", adj_key("b"))
            if va is None or vb is None:
                return False
            return (
                len(from_wire(va.value, AdjacencyDatabase).adjacencies) == 0
                and len(from_wire(vb.value, AdjacencyDatabase).adjacencies) == 0
            )

        ok = await settle(withdrawn, timeout=5.0)
        assert ok, "adjacency was not withdrawn after link down"
        for n in (a, b):
            for mod in ("lm", "spark", "client", "store"):
                await n[mod].stop()

    run(main())


def test_linkmonitor_flap_damping():
    async def main():
        hub = MockIoHub()
        transport = InProcKvTransport()
        n = _mk_node(hub, transport, "a")
        await n["store"].start()
        await n["client"].start()
        await n["spark"].start()
        await n["lm"].start()
        lm = n["lm"]
        # flap the interface rapidly
        for _ in range(4):
            lm.update_interface(InterfaceInfo(name="if-x", is_up=True))
            lm.update_interface(InterfaceInfo(name="if-x", is_up=False))
        lm.update_interface(InterfaceInfo(name="if-x", is_up=True))
        # damped: interface NOT immediately handed to spark
        assert "if-x" not in n["spark"].interfaces
        assert n["counters"].get("linkmonitor.flap_damped") > 0
        for mod in ("lm", "spark", "client", "store"):
            await n[mod].stop()

    run(main())


def test_node_overload_advertised():
    async def main():
        hub = MockIoHub()
        transport = InProcKvTransport()
        n = _mk_node(hub, transport, "a")
        for mod in ("store", "client", "spark", "lm"):
            await n[mod].start()
        n["lm"].set_node_overload(True)
        ok = await settle(
            lambda: (v := n["store"].get_key("0", adj_key("a"))) is not None
            and from_wire(v.value, AdjacencyDatabase).is_overloaded,
            timeout=3.0,
        )
        assert ok
        n["lm"].set_node_overload(False)
        ok = await settle(
            lambda: not from_wire(
                n["store"].get_key("0", adj_key("a")).value, AdjacencyDatabase
            ).is_overloaded,
            timeout=3.0,
        )
        assert ok
        for mod in ("lm", "spark", "client", "store"):
            await n[mod].stop()

    run(main())


@pytest.mark.asyncio_debug_off  # asserts wall-clock RTT bounds; debug
# mode's per-callback overhead inflates the measured 2x20ms link RTT
def test_rtt_measured_from_reflected_timestamps():
    """A 20ms one-way mock link → measured RTT ≈ 40ms (reference: Spark
    RTT from reflected hello timestamps minus neighbor turnaround lag †)."""

    async def main():
        hub = MockIoHub()
        sa, qa = mk_spark(hub, "a")
        sb, _qb = mk_spark(hub, "b")
        hub.link("a", "if-ab", "b", "if-ba", latency_ms=20)
        await sa.start()
        await sb.start()
        sa.add_interface("if-ab")
        sb.add_interface("if-ba")
        ok = await settle(
            lambda: (nb := sa.neighbors.get(("if-ab", "b"))) is not None
            and nb.rtt_us > 0,
            timeout=5.0,
        )
        assert ok, "rtt never measured"
        # let the EWMA settle over a few more hello exchanges
        await asyncio.sleep(0.5)
        rtt_ms = sa.neighbors[("if-ab", "b")].rtt_us / 1e3
        assert 25 < rtt_ms < 120, f"rtt {rtt_ms}ms implausible for 2x20ms link"
        await sa.stop()
        await sb.stop()

    run(main())
