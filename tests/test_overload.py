"""Overload-control tests: bounded messaging seams, KvStore flood
throttling to backed-off peers, Spark inbox bounds, backoff jitter, and
ctrl slow-subscriber eviction.

The seams under test are the ones ISSUE 4 bounds: every inter-module
queue gets a cap + overflow policy (openr_tpu/messaging), the per-peer
flood buffer absorbs publications while a peer is backed off and flushes
them as ONE coalesced message after heal, and telemetry consumers shed
instead of blocking producers.
"""

import asyncio
import random

import pytest

from openr_tpu.common.backoff import ExponentialBackoff
from openr_tpu.common.tasks import reap
from openr_tpu.config import Config, NodeConfig
from openr_tpu.messaging import (
    BLOCK,
    COALESCE,
    SHED_OLDEST,
    QueueClosedError,
    QueueFullError,
    ReplicateQueue,
)
from openr_tpu.messaging.policies import (
    coalesce_publications,
    coalesce_route_updates,
)
from openr_tpu.monitor import Counters
from openr_tpu.types.kvstore import Publication, Value


def run(coro):
    # asyncio.run: closes the loop, cancels leftovers, shuts down
    # async generators — the teardown hygiene the sanitizer checks
    return asyncio.run(coro)


# ------------------------------------------------------------ queue policies


def test_shed_oldest_policy_and_gauges():
    async def body():
        c = Counters()
        q = ReplicateQueue(
            name="n.logs", maxsize=3, policy=SHED_OLDEST,
            counters=c, counter_key="log_samples",
        )
        r = q.get_reader()
        for i in range(10):
            q.push(i)
        assert r.size() == 3 and r.shed == 7
        # the NEWEST items survive; the stalest were shed
        assert [await r.get() for _ in range(3)] == [7, 8, 9]
        assert c.get("queue.log_samples.shed") == 7
        assert c.get("queue.log_samples.highwater") == 3
        assert c.get("queue.log_samples.depth") == 0

    run(body())


def test_coalesce_policy_merges_at_the_bound():
    async def body():
        q = ReplicateQueue(
            name="n.routes", maxsize=2, policy=COALESCE,
            coalesce_fn=lambda tail, new: tail + new,
        )
        r = q.get_reader()
        for i in range(6):
            q.push(i)
        assert r.size() == 2 and r.coalesced == 4
        assert await r.get() == 0
        assert await r.get() == 1 + 2 + 3 + 4 + 5

    run(body())


def test_coalesce_unmergeable_overflows_instead_of_losing_data():
    async def body():
        q = ReplicateQueue(
            name="n.x", maxsize=1, policy=COALESCE,
            coalesce_fn=lambda tail, new: None,
        )
        r = q.get_reader()
        q.push("a")
        q.push("b")
        assert r.size() == 2  # admitted past the bound
        assert r.overflow == 1  # ... but counted

    run(body())


def test_block_policy_backpressures_producer():
    async def body():
        q = ReplicateQueue(name="n.b", maxsize=1, policy=BLOCK)
        r = q.get_reader()
        q.push("x")
        with pytest.raises(QueueFullError):
            q.push("y")  # sync push on a full block queue is an error
        landed = []

        async def producer():
            await q.put("y")  # waits for room
            landed.append("y")

        task = asyncio.get_event_loop().create_task(producer())
        await asyncio.sleep(0.02)
        assert not landed  # still blocked
        assert await r.get() == "x"  # consumer frees a slot ...
        await asyncio.sleep(0.02)
        assert landed  # ... and the producer completed
        assert await r.get() == "y"
        await task

    run(body())


def test_block_policy_push_is_all_or_nothing():
    """A push rejected by one full block reader must deliver to NOBODY —
    otherwise the documented retry (`await put`) duplicates the item on
    every reader that had room."""

    async def body():
        q = ReplicateQueue(name="n.b3", maxsize=1, policy=BLOCK)
        roomy, full = q.get_reader(), q.get_reader()
        q.push("a")
        assert await roomy.get() == "a"  # roomy has space again, full not
        writes = q.num_writes
        with pytest.raises(QueueFullError):
            q.push("b")
        assert roomy.size() == 0  # nothing partially delivered
        assert q.num_writes == writes

        async def drain_full():
            assert await full.get() == "a"

        task = asyncio.get_event_loop().create_task(drain_full())
        await q.put("b")  # retry path: exactly one copy everywhere
        await task
        assert await roomy.get() == "b" and await full.get() == "b"
        assert roomy.size() == 0 and full.size() == 0

    run(body())


def test_block_policy_close_releases_blocked_producer():
    async def body():
        q = ReplicateQueue(name="n.b2", maxsize=1, policy=BLOCK)
        q.get_reader()
        q.push(1)

        async def producer():
            try:
                await q.put(2)
            except QueueClosedError:
                return "closed"
            return "landed"

        task = asyncio.get_event_loop().create_task(producer())
        await asyncio.sleep(0.02)
        q.close()
        assert await task == "closed"

    run(body())


def test_per_reader_independence():
    """A slow reader sheds its OWN backlog; the fast reader loses
    nothing (the ReplicateQueue contract survives the bounds)."""

    async def body():
        q = ReplicateQueue(name="n.s", maxsize=2, policy=SHED_OLDEST)
        fast, slow = q.get_reader(), q.get_reader()
        for i in range(4):
            q.push(i)
            if i < 2:
                # fast reader keeps up for the first two items
                assert await fast.get() == i
        assert slow.size() == 2 and slow.shed == 2
        assert fast.shed == 0

    run(body())


# --------------------------------------------------------------- coalesce fns


def _v(version: int, origin: str = "a", payload: bytes = b"x") -> Value:
    return Value(
        version=version, originator_id=origin, value=payload
    ).with_hash()


def test_coalesce_publications_merge_semantics():
    p1 = Publication(
        area="0",
        key_vals={"k1": _v(1), "k2": _v(1)},
        expired_keys=["dead1"],
        node_ids=["a"],
    )
    p2 = Publication(
        area="0",
        key_vals={"k2": _v(2), "dead1": _v(3)},
        expired_keys=["k1"],
        node_ids=["b"],
    )
    m = coalesce_publications(p1, p2)
    # newest value wins; an expired-then-readvertised key is alive; an
    # updated-then-expired key is dead
    assert m.key_vals["k2"].version == 2
    assert "dead1" in m.key_vals and "dead1" not in m.expired_keys
    assert "k1" not in m.key_vals and "k1" in m.expired_keys
    assert m.node_ids == ["a", "b"]
    # tail is NOT mutated (it is shared with other readers)
    assert p1.key_vals["k2"].version == 1 and p1.expired_keys == ["dead1"]
    # cross-area publications don't merge
    assert coalesce_publications(p1, Publication(area="1")) is None


def test_coalesce_route_updates_folds_like_fib():
    from openr_tpu.types.network import IpPrefix, NextHop
    from openr_tpu.types.routes import RibEntry, RouteUpdate, RouteUpdateType

    def entry(p):
        return RibEntry(
            prefix=p,
            nexthops=(
                NextHop(address="n", if_name="if", metric=1, neighbor_node="n"),
            ),
        )

    pa, pb = IpPrefix.make("10.0.1.0/24"), IpPrefix.make("10.0.2.0/24")
    tail = RouteUpdate(
        unicast_to_update={pa: entry(pa)}, unicast_to_delete=[pb]
    )
    new = RouteUpdate(
        unicast_to_update={pb: entry(pb)}, unicast_to_delete=[pa]
    )
    m = coalesce_route_updates(tail, new)
    # delete-then-update resurrects; update-then-delete kills
    assert pb in m.unicast_to_update and pb not in m.unicast_to_delete
    assert pa not in m.unicast_to_update and pa in m.unicast_to_delete
    # a FULL_SYNC new supersedes everything pending
    full = RouteUpdate(
        type=RouteUpdateType.FULL_SYNC, unicast_to_update={pb: entry(pb)}
    )
    m2 = coalesce_route_updates(tail, full)
    assert m2.type == RouteUpdateType.FULL_SYNC
    assert set(m2.unicast_to_update) == {pb} and not m2.unicast_to_delete
    # folding a delta over a pending FULL_SYNC keeps the FULL_SYNC type
    # and drops deleted prefixes from the snapshot outright
    m3 = coalesce_route_updates(m2, RouteUpdate(unicast_to_delete=[pb]))
    assert m3.type == RouteUpdateType.FULL_SYNC
    assert not m3.unicast_to_update and not m3.unicast_to_delete


def test_node_queue_wiring_bounds_and_registry():
    """An OpenrNode built with a small cap wires the policied seams
    bounded: a publication burst coalesces in kvstore_pubs instead of
    growing the reader."""
    from dataclasses import replace

    from openr_tpu.kvstore import InProcKvTransport
    from openr_tpu.spark import MockIoHub
    from openr_tpu.node import OpenrNode

    async def body():
        ncfg = NodeConfig(node_name="x")
        ncfg = replace(ncfg, messaging=replace(ncfg.messaging, queue_maxsize=4))
        node = OpenrNode(
            Config(ncfg), MockIoHub().io_for("x"), InProcKvTransport()
        )
        assert set(node.queues) >= {
            "kvstore_pubs", "route_updates", "log_samples", "perf_events"
        }
        for i in range(20):  # nothing drains: the node is not started
            node.kvstore_pubs.push(
                Publication(area="0", key_vals={f"k{i}": _v(1)})
            )
        for r in node.kvstore_pubs.readers:
            assert r.size() <= 4 and r.highwater <= 4
            assert r.coalesced > 0
        # the tail item carries the coalesced burst
        tail = node.kvstore_pubs.readers[0]._items[-1]
        assert len(tail.key_vals) > 1

    run(body())


# ------------------------------------------------- kvstore flood throttling


def test_flood_pending_version_dominant_merge():
    """A stale value can never replace a newer one already queued for a
    peer (same total order as store.merge_key_values)."""
    from openr_tpu.kvstore.kvstore import KvStore, PeerSpec, _Peer

    async def body():
        kv = KvStore(
            Config(NodeConfig(node_name="a")),
            transport=None,
            publications_queue=ReplicateQueue(name="pubs"),
        )
        peer = _Peer(PeerSpec(node_name="b"))
        kv._enqueue_flood(
            peer, Publication(area="0", key_vals={"k": _v(5)})
        )
        kv._enqueue_flood(
            peer, Publication(area="0", key_vals={"k": _v(3)})
        )
        assert peer.pending_keys["k"].version == 5  # stale draw rejected
        kv._enqueue_flood(
            peer, Publication(area="0", key_vals={"k": _v(7)})
        )
        assert peer.pending_keys["k"].version == 7
        # a re-advertised key cannot stay in the pending-expired set
        peer.pending_expired.add("k")
        kv._enqueue_flood(
            peer, Publication(area="0", key_vals={"k": _v(8)})
        )
        assert "k" not in peer.pending_expired
        # a TTL refresh (hash-only, same writer generation, higher
        # ttl_version) must fold its ttl into the buffered FULL value —
        # never replace the payload with value=None
        full = peer.pending_keys["k"]
        refresh = Value(
            version=full.version,
            originator_id=full.originator_id,
            value=None,
            ttl=60_000,
            ttl_version=full.ttl_version + 1,
            hash=full.hash,
        )
        kv._enqueue_flood(
            peer, Publication(area="0", key_vals={"k": refresh})
        )
        buffered = peer.pending_keys["k"]
        assert buffered.value == full.value  # payload survives
        assert buffered.ttl_version == full.ttl_version + 1
        assert buffered.ttl == 60_000
        await kv.stop()

    run(body())


def test_flood_coalesces_to_backed_off_peer():
    """Acceptance: with a backed-off peer, N publications coalesce into
    ≪N flood messages after heal, and the stores end byte-identical."""
    from openr_tpu.emulator import Cluster, without_anti_entropy
    from openr_tpu.emulator.invariants import (
        check_kvstore_consistency,
        wait_quiescent,
    )

    N = 40

    async def body():
        c = Cluster.from_edges(
            [("a", "b")], node_config_transform=without_anti_entropy
        )
        await c.start()
        await c.wait_converged(timeout=20.0)
        na = c.nodes["a"]
        # b's process "dies" without the adjacency noticing: a's next
        # flood fails, the session drops, and the sync task backs off
        c.transport.unregister("b")
        na.kvstore.set_key(
            "0", "soak:kick", _v(1, origin="a")
        )
        t0 = asyncio.get_event_loop().time()
        while na.counters.get("kvstore.peer_disconnects") < 1:
            assert asyncio.get_event_loop().time() - t0 < 5.0
            await asyncio.sleep(0.01)
        floods_before = na.counters.get("kvstore.floods_sent")
        # N publications while the peer is sessionless: they must all
        # land in the pending buffer, version-dominantly merged
        for v in range(1, 3):
            for i in range(N // 2):
                na.kvstore.set_key(
                    "0",
                    f"soak:k{i}",
                    Value(
                        version=v, originator_id="a", value=b"x%d" % v
                    ).with_hash(),
                )
        peer = na.kvstore.peers[("0", "b")]
        assert peer.session is None
        assert len(peer.pending_keys) >= N // 2
        assert na.counters.get("kvstore.flood_keys_coalesced") >= N // 2
        # heal: the sync task re-establishes the session, then the
        # pump flushes the WHOLE backlog as one coalesced batch
        c.transport.register("b", c.nodes["b"].kvstore)
        t0 = asyncio.get_event_loop().time()
        while peer.pending_keys or not peer.synced:
            assert asyncio.get_event_loop().time() - t0 < 20.0, (
                f"backlog never flushed: {len(peer.pending_keys)} keys"
            )
            await asyncio.sleep(0.02)
        flood_calls = na.counters.get("kvstore.floods_sent") - floods_before
        assert flood_calls <= N // 4, (
            f"{N} publications produced {flood_calls} floods — "
            "coalescing is broken"
        )
        await wait_quiescent(c, timeout_s=20.0)
        assert check_kvstore_consistency(c) == []
        await c.stop()

    run(body())


# --------------------------------------------------------- spark inbox bound


def test_mock_hub_inbox_bound_sheds_oldest():
    from openr_tpu.spark.io import MockIoHub

    async def body():
        hub = MockIoHub(inbox_max=5)
        c = Counters()
        hub.set_counters("b", c)
        hub.io_for("a")
        hub.io_for("b")
        hub.link("a", "ifa", "b", "ifb")
        io_a = hub.io_for("a")
        for i in range(12):
            await io_a.send("ifa", b"pkt%d" % i)
        assert hub._inboxes["b"].qsize() == 5
        assert hub.inbox_drops["b"] == 7
        assert c.get("spark.inbox_dropped") == 7
        # the newest packets survived (periodic Spark traffic is
        # self-superseding, so shedding oldest is the correct policy)
        ifn, payload = hub._inboxes["b"].get_nowait()
        assert payload == b"pkt7"

    run(body())


def test_udp_provider_rx_bound():
    from openr_tpu.spark.io import UdpIoProvider

    async def body():
        p = UdpIoProvider(inbox_max=4)
        port = await p.add_interface("if0")
        p.set_peer("if0", ("127.0.0.1", port))  # self-loop
        for i in range(10):
            await p.send("if0", b"x%d" % i)
        await asyncio.sleep(0.2)
        assert p._rx.qsize() <= 4
        assert p.rx_dropped >= 6
        p.close()

    run(body())


# ------------------------------------------------------------ backoff jitter


def test_backoff_jitter_decorrelates_delays():
    rng = random.Random(1234)
    b = ExponentialBackoff(100, 10_000, jitter=True, rng=rng)
    delays, envelopes = [], []
    for _ in range(6):
        b.report_error()
        delays.append(b.delay_ms)
        envelopes.append(b.current_ms)
    # the envelope keeps exact deterministic doubling (saturation
    # detection relies on it) ...
    assert envelopes == [100, 200, 400, 800, 1600, 3200]
    # ... while the in-force delay is spread inside [envelope/2, envelope]
    assert all(e / 2 <= d <= e for d, e in zip(delays, envelopes))
    assert len(set(delays)) > 1
    # injectable RNG ⇒ reproducible
    b2 = ExponentialBackoff(100, 10_000, jitter=True, rng=random.Random(1234))
    d2 = []
    for _ in range(6):
        b2.report_error()
        d2.append(b2.delay_ms)
    assert d2 == delays
    b.report_success()
    assert b.delay_ms == 0.0 and b.current_ms == 0.0
    # two same-seed FAILURE HISTORIES with different RNG streams retry
    # at different instants — the thundering-herd decorrelation
    ba = ExponentialBackoff(100, 10_000, jitter=True, rng=random.Random(1))
    bb = ExponentialBackoff(100, 10_000, jitter=True, rng=random.Random(2))
    ba.report_error()
    bb.report_error()
    assert ba.delay_ms != bb.delay_ms


def test_backoff_default_unjittered_unchanged():
    b = ExponentialBackoff(8, 64)
    for want in (8, 16, 32, 64, 64):
        b.report_error()
        assert b.current_ms == want and b.delay_ms == want


# -------------------------------------------------- ctrl slow subscriber


def test_ctrl_slow_subscriber_evicts_oldest():
    """A stalled streaming subscriber loses its STALEST buffered update
    (counted as ctrl.sub_evictions); the fan-out never blocks and the
    subscriber keeps its stream."""
    from openr_tpu.ctrl import CtrlServer
    from openr_tpu.kvstore import InProcKvTransport
    from openr_tpu.spark import MockIoHub
    from openr_tpu.node import OpenrNode

    async def body():
        node = OpenrNode(
            Config(NodeConfig(node_name="x")),
            MockIoHub().io_for("x"),
            InProcKvTransport(),
        )
        server = CtrlServer(node)
        server.SUB_QUEUE_MAX = 4  # instance override: tiny buffer
        sub = server._add_sub(server._kv_subs)
        fan = asyncio.get_event_loop().create_task(
            server._fanout(
                server._kv_reader, server._kv_subs, server._encode_pub
            )
        )
        for i in range(10):
            node.kvstore_pubs.push(
                Publication(area="0", key_vals={f"k{i}": _v(1)})
            )
        t0 = asyncio.get_event_loop().time()
        while node.counters.get("ctrl.sub_evictions") < 6:
            assert asyncio.get_event_loop().time() - t0 < 5.0
            await asyncio.sleep(0.01)
        # subscriber still registered, buffer holds the NEWEST 4
        assert sub in server._kv_subs
        got = [sub.get_nowait() for _ in range(sub.qsize())]
        assert [sorted(p["key_vals"]) for p in got] == [
            [f"k{i}"] for i in range(6, 10)
        ]
        await reap(fan)

    run(body())


def test_ctrl_fanout_close_delivers_sentinel_to_full_subscriber():
    """Stream close must land the end-of-stream None even on a stalled
    subscriber sitting at exactly maxsize (it sheds one item) — and the
    remaining subscribers still get theirs."""
    from openr_tpu.ctrl import CtrlServer
    from openr_tpu.kvstore import InProcKvTransport
    from openr_tpu.spark import MockIoHub
    from openr_tpu.node import OpenrNode

    async def body():
        node = OpenrNode(
            Config(NodeConfig(node_name="x")),
            MockIoHub().io_for("x"),
            InProcKvTransport(),
        )
        server = CtrlServer(node)
        server.SUB_QUEUE_MAX = 2
        stalled = server._add_sub(server._kv_subs)
        healthy = server._add_sub(server._kv_subs)
        fan = asyncio.get_event_loop().create_task(
            server._fanout(
                server._kv_reader, server._kv_subs, server._encode_pub
            )
        )
        for i in range(2):
            node.kvstore_pubs.push(
                Publication(area="0", key_vals={f"k{i}": _v(1)})
            )
        while stalled.qsize() < 2:
            await asyncio.sleep(0.01)
        healthy.get_nowait(), healthy.get_nowait()  # healthy keeps up
        node.kvstore_pubs.close()
        await asyncio.wait_for(fan, timeout=5.0)  # close path completed
        drained = [stalled.get_nowait() for _ in range(stalled.qsize())]
        assert drained[-1] is None  # sentinel landed despite full queue
        assert healthy.get_nowait() is None

    run(body())
