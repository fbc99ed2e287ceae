"""KvStore tests (reference analogue: openr/kvstore/tests/KvStoreTest.cpp †
— the KvStoreWrapper pattern: N real stores wired in one process, testing
merge properties, flooding, full sync, TTL expiry, conflict resolution)."""

import asyncio

import pytest

from openr_tpu.config import Config
from openr_tpu.kvstore import (
    InProcKvTransport,
    KvStore,
    KvStoreClient,
    merge_key_values,
)
from openr_tpu.kvstore.kvstore import PeerSpec
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import Counters
from openr_tpu.types.kvstore import TTL_INFINITY, Publication, Value


def run(coro):
    # asyncio.run: closes the loop, cancels leftovers, shuts down
    # async generators — the teardown hygiene the sanitizer checks
    return asyncio.run(coro)


def V(version, orig, value, ttl=TTL_INFINITY, ttl_version=0):
    return Value(
        version=version,
        originator_id=orig,
        value=value,
        ttl=ttl,
        ttl_version=ttl_version,
    ).with_hash()


# ---- merge properties (reference: mergeKeyValues semantics †) -------------


def test_merge_higher_version_wins():
    store = {"k": V(1, "a", b"old")}
    acc, stale = merge_key_values(store, {"k": V(2, "z", b"new")})
    assert "k" in acc and store["k"].value == b"new"
    assert not stale


def test_merge_lower_version_reports_stale():
    store = {"k": V(5, "a", b"cur")}
    acc, stale = merge_key_values(store, {"k": V(3, "z", b"old")})
    assert not acc and stale == ["k"]
    assert store["k"].value == b"cur"


def test_merge_tie_originator_then_hash():
    store = {"k": V(2, "a", b"x")}
    acc, _ = merge_key_values(store, {"k": V(2, "b", b"y")})
    assert "k" in acc and store["k"].originator_id == "b"
    # same version+originator, different payload → larger hash wins
    v1, v2 = V(2, "b", b"p1"), V(2, "b", b"p2")
    lo, hi = sorted([v1, v2], key=lambda v: v.hash)
    store2 = {"k": lo}
    acc2, _ = merge_key_values(store2, {"k": hi})
    assert "k" in acc2 and store2["k"].hash == hi.hash
    # and the loser direction is rejected
    store3 = {"k": hi}
    acc3, stale3 = merge_key_values(store3, {"k": lo})
    assert not acc3 and stale3 == ["k"]


def test_merge_ttl_refresh_same_writer():
    store = {"k": V(2, "a", b"x", ttl=1000, ttl_version=1)}
    refresh = V(2, "a", None, ttl=5000, ttl_version=2)
    acc, _ = merge_key_values(store, {"k": refresh})
    assert "k" in acc
    assert store["k"].value == b"x"  # payload untouched
    assert store["k"].ttl == 5000 and store["k"].ttl_version == 2
    # stale ttl_version rejected
    acc2, stale2 = merge_key_values(store, {"k": V(2, "a", None, ttl=9000, ttl_version=0)})
    assert not acc2 and stale2 == ["k"]


def test_merge_is_idempotent_and_commutative():
    """Convergence property: any order of the same updates → same store."""
    import itertools

    updates = [
        {"k": V(1, "a", b"1")},
        {"k": V(2, "a", b"2")},
        {"k": V(2, "b", b"3")},
        {"j": V(1, "c", b"4")},
    ]
    finals = set()
    for perm in itertools.permutations(updates):
        store = {}
        for u in perm:
            merge_key_values(store, {k: V(v.version, v.originator_id, v.value) for k, v in u.items()})
        finals.add(tuple(sorted((k, v.version, v.originator_id, v.value) for k, v in store.items())))
    assert len(finals) == 1


# ---- multi-store wiring (KvStoreWrapper pattern) --------------------------


class Wrapper:
    """N in-process stores (reference: KvStoreWrapper †)."""

    def __init__(self, transport, name):
        self.q = ReplicateQueue(name=f"{name}.pubs")
        self.counters = Counters()
        self.config = Config.default(name)
        self.store = KvStore(
            self.config, transport, self.q, counters=self.counters
        )
        transport.register(name, self.store)
        self.reader = self.q.get_reader()

    async def start(self):
        await self.store.start()

    async def stop(self):
        await self.store.stop()


async def _mk_stores(transport, names):
    ws = {n: Wrapper(transport, n) for n in names}
    for w in ws.values():
        await w.start()
    return ws


async def _settle(cond, timeout=3.0, interval=0.01):
    t0 = asyncio.get_event_loop().time()
    while not cond():
        if asyncio.get_event_loop().time() - t0 > timeout:
            return False
        await asyncio.sleep(interval)
    return True


def test_flooding_line_topology():
    """a—b—c: a's write reaches c through b (split-horizon flood)."""

    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a", "b", "c"])
        # peer the line (both directions)
        ws["a"].store.add_peer_sync(PeerSpec(node_name="b"))
        ws["b"].store.add_peer_sync(PeerSpec(node_name="a"))
        ws["b"].store.add_peer_sync(PeerSpec(node_name="c"))
        ws["c"].store.add_peer_sync(PeerSpec(node_name="b"))
        await asyncio.sleep(0.05)
        ws["a"].store.set_key("0", "k1", V(1, "a", b"hello"))
        ok = await _settle(
            lambda: ws["c"].store.get_key("0", "k1") is not None
        )
        assert ok, "flood a→b→c failed"
        assert ws["c"].store.get_key("0", "k1").value == b"hello"
        # loop guard: a's pub must not boomerang as a new merge on a
        assert ws["a"].store.get_key("0", "k1").version == 1
        for w in ws.values():
            await w.stop()

    run(main())


def test_full_sync_on_peer_add():
    """Stores with divergent pre-existing state converge on peering:
    newer versions win in both directions (3-way sync)."""

    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a", "b"])
        ws["a"].store.set_key("0", "ka", V(1, "a", b"from-a"))
        ws["a"].store.set_key("0", "shared", V(3, "a", b"a-newer"))
        ws["b"].store.set_key("0", "kb", V(1, "b", b"from-b"))
        ws["b"].store.set_key("0", "shared", V(2, "b", b"b-older"))
        ws["a"].store.add_peer_sync(PeerSpec(node_name="b"))
        ws["b"].store.add_peer_sync(PeerSpec(node_name="a"))
        ok = await _settle(
            lambda: ws["a"].store.get_key("0", "kb") is not None
            and ws["b"].store.get_key("0", "ka") is not None
            and ws["b"].store.get_key("0", "shared") is not None
            and ws["b"].store.get_key("0", "shared").value == b"a-newer"
        )
        assert ok
        assert ws["a"].store.get_key("0", "shared").value == b"a-newer"
        assert ws["a"].store.initial_sync_done.is_set()
        for w in ws.values():
            await w.stop()

    run(main())


def test_full_sync_legacy_responder_fallback():
    """A pre-delta responder rejects the compact triple digest (its
    value_from_json chokes on a list) — the requester must flip that
    peer to the legacy dict-digest form and still converge
    (docs/Wire.md migration story), counting the fallback."""
    from openr_tpu.rpc import RpcError

    class LegacyResponderTransport(InProcKvTransport):
        """Emulates an old-build peer: triple digests and digestless
        probes come back as handler errors (what an RPC error reply
        surfaces as); legacy dict digests are served, with the delta
        trailer fields stripped from the reply."""

        async def connect(self, peer_id, endpoint, counters=None):
            session = await super().connect(
                peer_id, endpoint, counters=counters
            )
            orig = session.full_sync

            async def legacy_full_sync(area, sender_id, digest,
                                       store_hash=None):
                if digest is None or any(
                    isinstance(v, (list, tuple)) for v in digest.values()
                ):
                    raise RpcError(
                        "ValueError: cannot decode digest entry"
                    )
                raw = await orig(area, sender_id, digest, store_hash=None)
                for k in ("store_hash", "noop", "need_digest"):
                    raw.pop(k, None)
                return raw

            session.full_sync = legacy_full_sync
            return session

    async def main():
        t = LegacyResponderTransport()
        ws = await _mk_stores(t, ["new", "old"])
        ws["new"].store.set_key("0", "kn", V(1, "new", b"from-new"))
        ws["old"].store.set_key("0", "ko", V(1, "old", b"from-old"))
        ws["new"].store.add_peer_sync(PeerSpec(node_name="old"))
        # settle on the COUNTER, not just the key: the key lands at
        # _apply but kvstore.full_syncs increments after the awaited
        # 3-way flood-back — asserting between the two is a race
        ok = await _settle(
            lambda: ws["new"].store.get_key("0", "ko") is not None
            and ws["new"].counters.get("kvstore.full_syncs", 0) >= 1,
            timeout=8.0,  # attempt 1 fails, backoff (~100ms), retry
        )
        assert ok, "never converged against the legacy responder"
        assert ws["new"].counters.get("kvstore.full_syncs_legacy", 0) >= 1
        # the probe stays locked out: a legacy peer would answer a
        # digestless round with a full store dump, not a noop
        peer = ws["new"].store.peers[("0", "old")]
        assert peer.legacy_sync and not peer.probe_ok
        for w in ws.values():
            await w.stop()

    run(main())


def test_ttl_expiry_publishes():
    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a"])
        ws["a"].store.set_key("0", "ephemeral", V(1, "a", b"x", ttl=300))
        assert ws["a"].store.get_key("0", "ephemeral") is not None
        ok = await _settle(
            lambda: ws["a"].store.get_key("0", "ephemeral") is None,
            timeout=3.0,
        )
        assert ok, "key did not expire"
        # expiry publication reached subscribers
        expired = []
        while (item := ws["a"].reader.try_get()) is not None:
            expired += item.expired_keys
        assert "ephemeral" in expired
        await ws["a"].stop()

    run(main())


def test_client_persist_key_defends_against_overwrite():
    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a", "b"])
        ws["a"].store.add_peer_sync(PeerSpec(node_name="b"))
        ws["b"].store.add_peer_sync(PeerSpec(node_name="a"))
        client = KvStoreClient(
            ws["a"].store, "a", ws["a"].q.get_reader(), counters=ws["a"].counters
        )
        await client.start()
        client.persist_key("0", "adj:a", b"my-adjacencies")
        await asyncio.sleep(0.05)
        # another node overwrites with a higher version
        ws["b"].store.set_key("0", "adj:a", V(5, "b", b"imposter"))
        ok = await _settle(
            lambda: (v := ws["a"].store.get_key("0", "adj:a")) is not None
            and v.originator_id == "a"
            and v.value == b"my-adjacencies"
            and v.version > 5
        )
        assert ok, "client did not win back its key"
        # and b converges to a's re-advertisement
        ok2 = await _settle(
            lambda: (v := ws["b"].store.get_key("0", "adj:a")) is not None
            and v.originator_id == "a"
        )
        assert ok2
        await client.stop()
        for w in ws.values():
            await w.stop()

    run(main())


def test_client_ttl_refresh_keeps_key_alive():
    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a"])
        client = KvStoreClient(
            ws["a"].store, "a", ws["a"].q.get_reader(), counters=ws["a"].counters
        )
        await client.start()
        client.persist_key("0", "k", b"v", ttl_ms=1500)
        await asyncio.sleep(2.5)  # > ttl: refresh must have kept it alive
        v = ws["a"].store.get_key("0", "k")
        assert v is not None and v.ttl_version > 0
        client.unset_key("0", "k")
        ok = await _settle(
            lambda: ws["a"].store.get_key("0", "k") is None, timeout=4.0
        )
        assert ok, "key did not die after unset"
        await client.stop()
        await ws["a"].stop()

    run(main())


def test_grid_convergence_16_stores():
    """4x4 grid of stores: one write floods everywhere (the multi-node-
    without-a-cluster pattern, reference: KvStoreTest grid cases †)."""

    async def main():
        t = InProcKvTransport()
        names = [f"s{i}" for i in range(16)]
        ws = await _mk_stores(t, names)

        def nid(r, c):
            return f"s{r * 4 + c}"

        for r in range(4):
            for c in range(4):
                me = nid(r, c)
                for rr, cc in ((r + 1, c), (r, c + 1)):
                    if rr < 4 and cc < 4:
                        other = nid(rr, cc)
                        ws[me].store.add_peer_sync(PeerSpec(node_name=other))
                        ws[other].store.add_peer_sync(PeerSpec(node_name=me))
        await asyncio.sleep(0.1)
        ws["s0"].store.set_key("0", "corner", V(1, "s0", b"flood-me"))
        ok = await _settle(
            lambda: all(
                w.store.get_key("0", "corner") is not None
                for w in ws.values()
            ),
            timeout=5.0,
        )
        assert ok, "grid did not converge"
        for w in ws.values():
            await w.stop()

    run(main())


# ---- flood rate-limiting / backpressure (reference: floodLimiter_ +
# pendingPublicationsToFlood_ buffering in KvStore.cpp †) -------------------


def test_flood_rate_limit_coalesces_same_key():
    """Under rapid same-key churn a rate-limited peer link carries the
    newest version in few messages, not every intermediate version."""

    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a", "b"])
        # throttle a's flooding hard BEFORE the first write (the drain
        # task snapshots the rate when it spawns on first flood)
        kv = ws["a"].config.node.kvstore
        kv.flood_rate_msgs_per_sec = 20
        kv.flood_rate_burst_size = 1
        ws["a"].store.add_peer_sync(PeerSpec(node_name="b"))
        ws["b"].store.add_peer_sync(PeerSpec(node_name="a"))
        await asyncio.sleep(0.05)

        n = 50
        for ver in range(1, n + 1):
            ws["a"].store.set_key("0", "churny", V(ver, "a", b"v%d" % ver))
        ok = await _settle(
            lambda: (v := ws["b"].store.get_key("0", "churny")) is not None
            and v.version == n,
            timeout=5.0,
        )
        assert ok, "rate-limited flood never converged"
        sent = ws["a"].counters.get("kvstore.floods_sent")
        coalesced = ws["a"].counters.get("kvstore.flood_keys_coalesced")
        # 50 versions must NOT mean 50 messages on the throttled link
        assert sent <= 10, f"sent {sent} floods for {n} coalescable updates"
        assert coalesced > 0
        for w in ws.values():
            await w.stop()

    run(main())


def test_flood_backpressure_overflow_resyncs():
    """A peer whose pending queue overflows gets its backlog dropped and
    repaired by one FULL_SYNC — bounded memory under any churn rate."""

    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a", "b"])
        kv = ws["a"].config.node.kvstore
        kv.flood_rate_msgs_per_sec = 1  # slow enough to pile up
        kv.flood_rate_burst_size = 1
        kv.flood_pending_max_keys = 8
        ws["a"].store.add_peer_sync(PeerSpec(node_name="b"))
        ws["b"].store.add_peer_sync(PeerSpec(node_name="a"))
        await asyncio.sleep(0.05)

        peer = ws["a"].store.peers[("0", "b")]
        n = 100
        for i in range(n):
            ws["a"].store.set_key("0", f"k{i}", V(1, "a", b"x"))
            assert len(peer.pending_keys) <= kv.flood_pending_max_keys
        assert ws["a"].counters.get("kvstore.flood_backpressure_drops") > 0
        # the scheduled FULL_SYNC repairs everything the drops carried
        ok = await _settle(
            lambda: all(
                ws["b"].store.get_key("0", f"k{i}") is not None
                for i in range(n)
            ),
            timeout=5.0,
        )
        assert ok, "backpressure resync did not converge"
        for w in ws.values():
            await w.stop()

    run(main())


class _HeldSyncTransport(InProcKvTransport):
    """In-proc transport whose full-sync replies can be held on the wire
    and whose floods can be made to fail, one switch each."""

    def __init__(self):
        super().__init__()
        self.release_sync = asyncio.Event()
        self.sync_replies_held = 0
        self.fail_floods = False

    async def connect(self, peer_id, endpoint, counters=None):
        return _HeldSyncSession(
            await super().connect(peer_id, endpoint, counters=counters), self
        )


class _HeldSyncSession:
    def __init__(self, inner, transport):
        self._inner = inner
        self._t = transport
        self.codec = inner.codec

    async def full_sync(self, area, sender_id, digest, store_hash=None):
        reply = await self._inner.full_sync(
            area, sender_id, digest, store_hash=store_hash
        )
        # answered from the responder's store as it is now; the answer
        # is then in flight until the test lets it land
        self._t.sync_replies_held += 1
        await self._t.release_sync.wait()
        return reply

    async def flood(self, pub):
        if self._t.fail_floods:
            raise ConnectionError("test: flood failed")
        return await self._inner.flood(pub)

    async def close(self):
        await self._inner.close()


@pytest.mark.parametrize("how", ["flood_fails", "backlog_overflows"])
def test_resync_asked_for_during_a_sync_is_not_lost(how):
    """A sync with the peer is in flight (its digest sent, the reply on
    its way) when the flood pump drops updates and asks for a re-sync to
    carry them: a flood on the session fails, or the backlog overflows.
    `_spawn_sync` keeps one task a peer, so the request finds the task
    running — and the running exchange predates what was dropped. It
    must go round again; ending as a success left the updates (and,
    after a failed flood, a peer with no session, no sync task and every
    later flood held) to the periodic anti-entropy sync, which this test
    keeps out of reach."""

    async def main():
        t = _HeldSyncTransport()
        ws = {n: Wrapper(t, n) for n in ("a", "b")}
        kv = ws["a"].config.node.kvstore
        for w in ws.values():
            w.config.node.kvstore.sync_interval_s = 3600
            await w.start()
        a, b = ws["a"].store, ws["b"].store
        a.add_peer_sync(PeerSpec(node_name="b"))
        assert await _settle(lambda: t.sync_replies_held == 1)
        peer = a.peers[("0", "b")]
        assert peer.session is not None and not peer.sync_task.done()

        if how == "flood_fails":
            keys = ["k0"]
            t.fail_floods = True
            a.set_key("0", "k0", V(1, "a", b"x"))
            assert await _settle(
                lambda: ws["a"].counters.get("kvstore.flood_failures") == 1
            )
            assert peer.session is None and not peer.pending_keys
            t.fail_floods = False
        else:
            keys = [f"k{i}" for i in range(5)]
            kv.flood_pending_max_keys = 4
            for k in keys:  # no await: the pump never gets to send one
                a.set_key("0", k, V(1, "a", b"x"))
            assert ws["a"].counters.get("kvstore.flood_backpressure_drops") == 5
            assert not peer.pending_keys
        assert not peer.sync_task.done()  # still the same exchange

        t.release_sync.set()
        ok = await _settle(
            lambda: all(b.get_key("0", k) is not None for k in keys),
            timeout=2.0,
        )
        assert ok, (
            f"b never got {keys}: peer synced={peer.synced} "
            f"session={peer.session} sync_task.done={peer.sync_task.done()}"
        )
        assert await _settle(lambda: peer.synced and peer.sync_task.done())
        assert peer.session is not None
        # and the flood path to b works again, nothing held
        a.set_key("0", "later", V(1, "a", b"y"))
        assert await _settle(lambda: b.get_key("0", "later") is not None)
        assert not peer.pending_keys
        for w in ws.values():
            await w.stop()

    run(main())


def test_anti_entropy_repairs_a_flood_lost_in_silence():
    """What is left for the backstop: a flood the transport reports as
    delivered and the peer never applied. Neither end can know, so no
    event repairs it; the periodic full sync does, a tick later."""

    class SilentLossTransport(InProcKvTransport):
        lose = False

        async def connect(self, peer_id, endpoint, counters=None):
            session = await super().connect(peer_id, endpoint, counters=counters)
            deliver = session.flood

            async def flood(pub):
                if self.lose:
                    return 0  # "sent", and gone
                return await deliver(pub)

            session.flood = flood
            return session

    async def main():
        t = SilentLossTransport()
        ws = {n: Wrapper(t, n) for n in ("a", "b")}
        for w in ws.values():
            w.config.node.kvstore.sync_interval_s = 1
            await w.start()
        a, b = ws["a"].store, ws["b"].store
        a.add_peer_sync(PeerSpec(node_name="b"))
        assert await _settle(
            lambda: ("0", "b") in a.peers and a.peers[("0", "b")].synced
        )
        t.lose = True
        a.set_key("0", "k", V(1, "a", b"x"))
        assert await _settle(
            lambda: ws["a"].counters.get("kvstore.floods_sent") == 1
        )
        t.lose = False
        assert b.get_key("0", "k") is None
        assert ws["a"].counters.get("kvstore.flood_failures") == 0
        ok = await _settle(
            lambda: b.get_key("0", "k") is not None, timeout=4.0
        )
        assert ok, "the periodic full sync did not repair the loss"
        for w in ws.values():
            await w.stop()

    run(main())


def test_flood_churn_1k_updates_per_sec_bounded():
    """Sustained 1k key-updates/sec against the default limiter: queue
    depth stays bounded and the peer converges to final state."""

    async def main():
        t = InProcKvTransport()
        ws = await _mk_stores(t, ["a", "b"])
        ws["a"].store.add_peer_sync(PeerSpec(node_name="b"))
        ws["b"].store.add_peer_sync(PeerSpec(node_name="a"))
        await asyncio.sleep(0.05)

        peer = ws["a"].store.peers[("0", "b")]
        kv = ws["a"].config.node.kvstore
        n_keys, rounds = 100, 10  # 1,000 updates over ~1s
        max_depth = 0
        loop = asyncio.get_event_loop()
        t0 = loop.time()
        ver = 0
        for r in range(rounds):
            ver += 1
            for i in range(n_keys):
                ws["a"].store.set_key("0", f"c{i}", V(ver, "a", b"r%d" % r))
            max_depth = max(max_depth, len(peer.pending_keys))
            # pace to ~100 updates per 100ms
            await asyncio.sleep(max(0.0, (r + 1) * 0.1 - (loop.time() - t0)))
        assert max_depth <= kv.flood_pending_max_keys
        ok = await _settle(
            lambda: all(
                (v := ws["b"].store.get_key("0", f"c{i}")) is not None
                and v.version == rounds
                for i in range(n_keys)
            ),
            timeout=5.0,
        )
        assert ok, "churn did not converge to final versions"
        for w in ws.values():
            await w.stop()

    run(main())
