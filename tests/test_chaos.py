"""Chaos soak + invariant checker tests (reference analogue: OpenrTest
churn scenarios †, driven here by the seeded deterministic fault layer
in openr_tpu/emulator/chaos.py).

Three fixed-seed storm archetypes — lossy transports, partition+heal,
crash+restart — run on a 9-node grid on BOTH solver paths (cpu oracle
and the TPU backend, CPU-emulated under JAX_PLATFORMS=cpu); after the
storm the cluster must quiesce and pass all four invariant classes
(emulator/invariants.py). Schedule determinism and seed-replayable
failure messages are asserted separately, without spinning a cluster.
"""

import asyncio

import pytest

# cluster-scale seeded storms: asyncio debug mode's per-task traceback
# capture is a ~10x tax that blows the convergence budgets; the
# sanitizer's leak checks stay fully active (tests/conftest.py)
pytestmark = pytest.mark.asyncio_debug_off

from openr_tpu.emulator import Cluster, without_anti_entropy
from openr_tpu.emulator.chaos import (
    ChaosPlan,
    FibFaults,
    KvFaults,
    LinkFaults,
    run_schedule,
)
from openr_tpu.emulator.invariants import (
    assert_invariants,
    wait_quiescent,
)
from openr_tpu.fib.fib import FibProgramError, MockFibHandler


def run(coro):
    # asyncio.run: closes the loop, cancels leftovers, shuts down
    # async generators — the teardown hygiene the sanitizer checks
    return asyncio.run(coro)


def grid_edges(n: int = 3) -> list[tuple[str, str]]:
    edges = []
    for r in range(n):
        for c in range(n):
            if c < n - 1:
                edges.append((f"n{r}{c}", f"n{r}{c + 1}"))
            if r < n - 1:
                edges.append((f"n{r}{c}", f"n{r + 1}{c}"))
    return edges


# --------------------------------------------------------------- determinism


STORM_ARGS = dict(
    duration_s=2.0, n_flaps=4, n_crashes=2, n_partitions=1, heal_after_s=0.5
)


def _built_plan(seed: int) -> ChaosPlan:
    plan = ChaosPlan(
        seed,
        link_faults=LinkFaults(drop=0.1, reorder=0.1, jitter_ms=30.0),
        kv_faults=KvFaults(fail_flood=0.1),
        fib_faults=FibFaults(fail_rate=0.05),
    )
    plan.build_storm(grid_edges(), [a for a, _ in grid_edges()], **STORM_ARGS)
    return plan


def test_schedule_hash_deterministic():
    """Same seed + same builder args → the identical fault schedule;
    a different seed diverges (the replayability contract)."""
    p1, p2 = _built_plan(42), _built_plan(42)
    assert p1.events == p2.events
    assert p1.events  # non-empty: the storm really scheduled something
    assert p1.schedule_hash() == p2.schedule_hash()
    p3 = _built_plan(43)
    assert p3.schedule_hash() != p1.schedule_hash()
    # heals never precede their fault, and events are time-sorted
    assert all(
        p1.events[i].at_s <= p1.events[i + 1].at_s
        for i in range(len(p1.events) - 1)
    )


def test_rng_streams_independent():
    """Consuming one seam's substream must not perturb another's —
    that is what keeps per-seam decisions seed-stable even when seams
    interleave differently across runs."""
    a = ChaosPlan(7)
    b = ChaosPlan(7)
    a.rng("io").random()  # perturb io before touching kv
    assert a.rng("kv").random() == b.rng("kv").random()


# ---------------------------------------------------------- fault primitives


def test_fail_link_unknown_pair_raises():
    c = Cluster.from_edges([("a", "b")])
    with pytest.raises(ValueError):
        c.fail_link("a", "zz")
    with pytest.raises(ValueError):
        c.heal_link("zz", "b")


def test_mock_fib_handler_rate_failures():
    """Rate-based injection beyond the count-only fail_next_n: a seeded
    RNG drives per-op failures, so a replay fails the same ops."""

    class _Always:
        def random(self):
            return 0.0

    class _Never:
        def random(self):
            return 1.0

    async def body():
        h = MockFibHandler(fail_rate=0.5, rng=_Always())
        with pytest.raises(FibProgramError):
            await h.add_unicast_routes(0, [])
        assert h.fail_count == 1
        h2 = MockFibHandler(fail_rate=0.5, rng=_Never())
        await h2.add_unicast_routes(0, [])
        assert h2.fail_count == 0

    run(body())


def test_chaos_fib_handler_inactive_still_honors_fail_next_n():
    """Plan-gated handler: clearing plan.active suppresses only the
    RATE faults — the count-based fail_next_n contract keeps working
    for deterministic post-storm injection."""
    from openr_tpu.emulator.chaos import ChaosFibHandler

    async def body():
        plan = ChaosPlan(1, fib_faults=FibFaults(fail_rate=1.0))
        h = ChaosFibHandler(plan, "x")
        plan.active = False
        await h.add_unicast_routes(0, [])  # rate=1.0 suppressed
        h.fail_next_n = 1
        with pytest.raises(FibProgramError):
            await h.add_unicast_routes(0, [])

    run(body())


def test_build_storm_graceful_crash_modes():
    """graceful_crashes: True → all GR, False → all hard, None → mix
    drawn from the seeded schedule stream."""
    links = [("a", "b"), ("b", "c"), ("c", "d")]
    nodes = ["a", "b", "c", "d"]
    for mode, want in ((True, {True}), (False, {False})):
        p = ChaosPlan(9)
        p.build_storm(
            links, nodes, duration_s=2.0, n_crashes=3,
            graceful_crashes=mode,
        )
        flags = {e.target[1] for e in p.events if e.kind == "crash"}
        assert flags == want, (mode, flags)


def test_kvstore_flood_failure_counters():
    """Satellite: _Peer.flood_failures is now surfaced as the
    kvstore.flood_failures / kvstore.peer_disconnects counters."""

    async def body():
        c = Cluster.from_edges([("a", "b")])
        await c.start()
        await c.wait_converged(timeout=20.0)
        na = c.nodes["a"]
        # simulate b's process dying without the adjacency noticing yet:
        # a's next flood hits a dead in-proc store and must fail
        c.transport.unregister("b")
        from openr_tpu.types.kvstore import Value

        na.kvstore.set_key(
            "0",
            "test:chaos-counter",
            Value(version=1, originator_id="a", value=b"x").with_hash(),
        )

        def failed():
            return na.counters.get("kvstore.flood_failures") >= 1

        t0 = asyncio.get_event_loop().time()
        while not failed():
            assert asyncio.get_event_loop().time() - t0 < 5.0, (
                "flood failure never surfaced in counters"
            )
            await asyncio.sleep(0.02)
        assert na.counters.get("kvstore.peer_disconnects") >= 1
        c.transport.register("b", c.nodes["b"].kvstore)  # let teardown sync
        await c.stop()

    run(body())


def test_fib_backoff_saturation_visibility(caplog):
    """Satellite: a persistently failing FibService pins the backoff at
    max_retry_ms — the streak counter grows and the saturation warning
    fires exactly once per episode, then success clears both."""
    import logging

    from openr_tpu.config import Config, NodeConfig
    from openr_tpu.fib import Fib
    from openr_tpu.messaging import ReplicateQueue
    from openr_tpu.monitor import Counters
    from openr_tpu.types.network import IpPrefix, NextHop
    from openr_tpu.types.routes import RibEntry, RouteUpdate, RouteUpdateType

    async def body():
        cfg = Config(NodeConfig(node_name="node-0"))
        cfg.node.fib.initial_retry_ms = 1
        cfg.node.fib.max_retry_ms = 4
        routes = ReplicateQueue(name="routes")
        handler = MockFibHandler()
        handler.fail_next_n = 6
        fib = Fib(
            cfg, routes.get_reader(), handler, counters=Counters()
        )
        await fib.start()
        p = IpPrefix.make("10.0.1.0/24")
        routes.push(
            RouteUpdate(
                type=RouteUpdateType.FULL_SYNC,
                unicast_to_update={
                    p: RibEntry(
                        prefix=p,
                        nexthops=(
                            NextHop(
                                address="n1", if_name="if-n1",
                                metric=1, neighbor_node="n1",
                            ),
                        ),
                    )
                },
            )
        )
        t0 = asyncio.get_event_loop().time()
        while not fib.synced.is_set():
            assert asyncio.get_event_loop().time() - t0 < 5.0
            await asyncio.sleep(0.005)
        assert fib.counters.get("fib.program_fail") >= 6
        # success cleared the streak after the failure burst
        assert fib.counters.get("fib.program_fail_streak") == 0
        saturated = [
            r for r in caplog.records
            if "backoff saturated" in r.getMessage()
        ]
        assert len(saturated) == 1, (
            "saturation warning must fire exactly once per episode"
        )
        await fib.stop()

    with caplog.at_level(logging.WARNING, logger="openr_tpu.fib.fib"):
        run(body())


# ------------------------------------------------------- seed-in-the-failure


def test_invariant_failure_message_carries_seed():
    async def body():
        plan = ChaosPlan(1234)
        c = Cluster.from_edges(
            [("a", "b")], chaos=plan,
            node_config_transform=without_anti_entropy,
        )
        await c.start()
        await c.wait_converged(timeout=20.0)
        plan.active = False
        await wait_quiescent(c, timeout_s=20.0, context=plan.replay_hint())
        # poison one counter identity: the checker must fail AND name
        # the seed needed to replay the run
        c.nodes["a"].counters.increment("decision.spf_runs", 5)
        with pytest.raises(AssertionError) as ei:
            assert_invariants(c, context=plan.replay_hint())
        assert "seed=1234" in str(ei.value)
        assert "counters.rebuild_sum" in str(ei.value)
        await c.stop()

    run(body())


# ------------------------------------------------------------ the chaos soaks


SCENARIOS = {
    # every seam lossy at once: spark packets drop/duplicate/reorder,
    # kv sessions fail and stall, the dataplane rejects ~5% of ops —
    # plus a handful of link flaps to force real topology churn
    "lossy_transport": dict(
        seed=101,
        link_faults=LinkFaults(
            drop=0.10, dup=0.05, reorder=0.10, jitter_ms=40.0
        ),
        kv_faults=KvFaults(
            fail_full_sync=0.10, fail_flood=0.10, delay_ms=5.0
        ),
        fib_faults=FibFaults(fail_rate=0.05),
        storm=dict(duration_s=1.6, n_flaps=5, heal_after_s=0.6),
    ),
    # clean split + heal: cross-group spark links down AND kv sessions
    # refused, then everything re-syncs after the heal
    "partition_heal": dict(
        seed=202,
        kv_faults=KvFaults(fail_flood=0.05),
        storm=dict(
            duration_s=2.2, n_flaps=2, n_partitions=1, heal_after_s=0.8
        ),
    ),
    # graceful-restart storm: two nodes crash (announcing GR) and come
    # back, warm-booting their fibs off the surviving dataplane
    "crash_restart": dict(
        seed=303,
        storm=dict(
            duration_s=2.2, n_flaps=2, n_crashes=2, heal_after_s=0.8
        ),
    ),
}


# What the nine-node grid needs, times three (PR 35, 120 runs of the six
# cases, twelve at once on eight cores): bring-up under the lossy plan
# took at most 2.50 s (the first solve of a process compiles), and from
# the storm's last event to two clean invariant checks at most 0.76 s
# (the checks are 0.25 s apart). Every repair in that window is driven
# by an event; nothing waits for a timer longer than a sync retry's
# first backoff steps (100, 200, 400 ms).
CONVERGE_BUDGET_S = 8.0
QUIESCE_BUDGET_S = 3.0


@pytest.mark.parametrize("solver", ["cpu", "tpu"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_chaos_soak(scenario, solver):
    spec = SCENARIOS[scenario]

    async def body():
        plan = ChaosPlan(
            spec["seed"],
            link_faults=spec.get("link_faults"),
            kv_faults=spec.get("kv_faults"),
            fib_faults=spec.get("fib_faults"),
        )
        # KvStore's periodic full sync is a day away: an update the
        # storm lost fails the quiesce check below, it cannot be
        # repaired by a tick and pass at 60 s (PR 35: 9 of 24 runs of
        # the four fault-injecting cases did)
        c = Cluster.from_edges(
            grid_edges(3), solver=solver, chaos=plan,
            node_config_transform=without_anti_entropy,
        )
        assert len(c.nodes) == 9
        await c.start()
        await c.wait_converged(timeout=CONVERGE_BUDGET_S)
        c.make_storm(plan, **spec["storm"])
        assert plan.events, "storm scheduled nothing"
        await run_schedule(c, plan)
        # post-storm: rate faults off (run_schedule cleared plan.active),
        # structural faults healed by their own events — now the cluster
        # must quiesce into all four invariant classes
        await wait_quiescent(
            c, timeout_s=QUIESCE_BUDGET_S, context=plan.replay_hint()
        )
        if scenario == "crash_restart":
            restarted = [
                e.target[0] for e in plan.events if e.kind == "crash"
            ]
            assert restarted
            for name in restarted:
                assert name in c.nodes, f"{name} never restarted"
        await c.stop()

    run(body())


# ------------------------------------------------------- dead-node TTL death


def test_dead_node_keys_expire_and_routes_reroute():
    """Satellite (ISSUE 4): a node that crashes PERMANENTLY (no restart,
    no graceful announcement) must fade out of the control plane by TTL
    alone — `_ttl_tick` on every surviving store expires its adj/prefix
    keys, Decision drops the routes through and to it, and the cluster
    settles into all invariants with traffic rerouted around the hole."""
    from openr_tpu.common import constants as C
    from openr_tpu.config import KvstoreConfig, NodeConfig, OriginatedPrefix
    from openr_tpu.emulator.cluster import (
        FAST_SPARK,
        ClusterNodeSpec,
        LinkSpec,
        loopback_of,
    )

    TTL_MS = 1500

    async def body():
        names = ["a", "b", "c", "d"]
        specs = [
            ClusterNodeSpec(
                name=n,
                config=NodeConfig(
                    node_name=n,
                    spark=FAST_SPARK,
                    kvstore=KvstoreConfig(key_ttl_ms=TTL_MS),
                    originated_prefixes=(
                        OriginatedPrefix(prefix=loopback_of(i)),
                    ),
                ),
            )
            for i, n in enumerate(names)
        ]
        links = [
            LinkSpec(a="a", b="b"), LinkSpec(a="b", b="c"),
            LinkSpec(a="c", b="d"), LinkSpec(a="d", b="a"),
        ]
        c = Cluster.build(
            specs, links, node_config_transform=without_anti_entropy
        )
        await c.start()
        await c.wait_converged(timeout=20.0)
        dead_loopback = None
        for r in c.nodes["a"].fib.get_programmed_unicast():
            if str(r.dest) == loopback_of(1):
                dead_loopback = r.dest
        assert dead_loopback is not None

        await c.crash_node("b", graceful=False)  # hard crash, never returns

        def dead_keys_everywhere_gone() -> bool:
            for node in c.nodes.values():
                for key in node.kvstore.dbs["0"].kv:
                    if key == C.adj_key("b") or key.startswith("prefix:b"):
                        return False
            return True

        t0 = asyncio.get_event_loop().time()
        while not dead_keys_everywhere_gone():
            assert asyncio.get_event_loop().time() - t0 < 30.0, (
                "dead node's keys never expired from surviving stores"
            )
            await asyncio.sleep(0.1)
        for node in c.nodes.values():
            assert node.counters.get("kvstore.expired_keys") >= 1

        # full quiescence: all invariant classes on the 3-node remainder
        await wait_quiescent(c, timeout_s=30.0, context="dead-node ttl")
        # the ring healed around the hole: a still reaches c and d ...
        for name, node in c.nodes.items():
            others = {loopback_of(i) for i, n in enumerate(names) if n != name}
            others.discard(loopback_of(1))  # ... but b's loopback is GONE
            programmed = {
                str(r.dest) for r in node.fib.get_programmed_unicast()
            }
            assert others <= programmed, (name, others - programmed)
            assert loopback_of(1) not in programmed, (
                f"{name} still routes to the dead node's loopback"
            )
        # a→c no longer transits b: the nexthop swings to the d side
        route_ac = {
            str(r.dest): r for r in c.nodes["a"].fib.get_programmed_unicast()
        }[loopback_of(2)]
        assert all("if-a-d" == nh.if_name for nh in route_ac.nexthops)
        await c.stop()

    run(body())


# --------------------------------------------------- warm boot under restart


def test_crash_restart_warm_boot_continuity():
    """Satellite: a crash-restarted node warm-boots off its surviving
    dataplane — fib.warm_boot_routes > 0, no full sync_fib pass, and
    ZERO route withdrawals for prefixes whose reachability survived the
    restart (the forwarding-never-gaps contract of GR + warm boot)."""

    async def body():
        c = Cluster.from_edges(
            [("a", "b"), ("b", "c"), ("c", "d"), ("d", "a")],
            node_config_transform=without_anti_entropy,
        )
        await c.start()
        await c.wait_converged(timeout=20.0)
        # full quiescence, not just route COUNTS: the ring's equal-cost
        # second nexthop can land after wait_converged under suite load,
        # and the continuity assertions below compare exact route sets
        await wait_quiescent(c, timeout_s=20.0)
        target = "b"
        handler = c.nodes[target].fib_handler
        from openr_tpu.fib.fib import CLIENT_ID_OPENR

        before = dict(handler.unicast.get(CLIENT_ID_OPENR, {}))
        assert len(before) == 3  # routes to the other three loopbacks
        sync0 = handler.sync_count
        deleted = []
        orig_del = handler.delete_unicast_routes

        async def spy_delete(client_id, prefixes):
            deleted.extend(prefixes)
            return await orig_del(client_id, prefixes)

        handler.delete_unicast_routes = spy_delete

        await c.crash_node(target, graceful=True)
        # the dataplane must hold the routes while the control plane is
        # down — that is the whole point of graceful restart
        assert dict(handler.unicast.get(CLIENT_ID_OPENR, {})) == before
        await asyncio.sleep(0.2)  # control plane stays down for a beat
        await c.restart_node(target)
        await c.wait_converged(timeout=20.0)
        nb = c.nodes[target]
        await nb.wait_initialized(timeout=20.0)

        assert nb.counters.get("fib.warm_boot_routes") > 0
        # warm boot programs an incremental delta, never a full sync
        assert handler.sync_count == sync0
        # zero route-withdrawal gap: no surviving prefix was ever deleted
        assert not deleted, f"withdrawal gap on {deleted}"
        after = dict(handler.unicast.get(CLIENT_ID_OPENR, {}))
        assert set(after) == set(before)
        await c.stop()

    run(body())
