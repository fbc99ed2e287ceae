"""Seeded long-horizon soak runner: back-to-back chaos storms over
sustained background prefix churn, with per-round invariant gates.

The short storms in tests/test_chaos.py prove the recovery machinery
converges once; production outages look different — *minutes* of
overlapping flaps while the control plane keeps originating and
withdrawing prefixes, which is exactly the regime where unbounded queues
grow and slow leaks hide. This runner composes PR 3's ``ChaosPlan``
storms for N rounds over a continuous churn generator and, after every
round's quiescence, enforces:

  * all five cluster invariant classes (``emulator/invariants.py``),
    including the bounded-queue-depth watermark check, and
  * a **monotone-memory watermark**: RSS and live-object count after
    round r must stay within tolerance of the post-round-1 baseline
    (round 1 absorbs warmup: JAX compilation caches, interned wire
    bytes) — the leak class a single short storm can never surface.

Every failure message embeds ``seed=<s> round=<r>`` plus the plan's
schedule hash, so a failing soak replays from its printout:
``python -m openr_tpu.emulator --soak --seed <s> --rounds <r+1>``.
"""

from __future__ import annotations

import asyncio
import gc
import logging
from dataclasses import dataclass, field, replace

from openr_tpu.common.tasks import guard_task, reap
from openr_tpu.emulator.chaos import (
    ChaosPlan,
    FibFaults,
    KvFaults,
    LinkFaults,
    run_schedule,
)
from openr_tpu.emulator.cluster import Cluster, without_anti_entropy
from openr_tpu.emulator.invariants import wait_quiescent
from openr_tpu.monitor import work_ledger
from openr_tpu.watchdog.watchdog import _current_rss_mb

log = logging.getLogger(__name__)


class SoakError(AssertionError):
    """An invariant or watermark breach; the message carries the seed and
    round needed to replay the failing run."""


@dataclass
class SoakConfig:
    seed: int = 7
    rounds: int = 3
    edges: list = field(default_factory=list)  # [(a, b)] — required
    solver: str = "cpu"
    # per-round storm shape (fed to Cluster.make_storm)
    storm_duration_s: float = 1.6
    n_flaps: int = 3
    n_crashes: int = 1
    n_partitions: int = 0
    #: disk-fault crash archetypes per round (multi-process soaks only:
    #: the in-process cluster has no persist plane to damage)
    n_disk_faults: int = 0
    heal_after_s: float = 0.6
    # rate faults active during each storm
    link_faults: LinkFaults = field(
        default_factory=lambda: LinkFaults(drop=0.05, reorder=0.05, jitter_ms=20.0)
    )
    kv_faults: KvFaults = field(
        default_factory=lambda: KvFaults(fail_flood=0.05)
    )
    fib_faults: FibFaults = field(default_factory=FibFaults)
    # background churn: advertise/withdraw cadence per churn step
    churn_interval_s: float = 0.03
    churn_prefixes: int = 12  # fixed pool size (fixed pool ⇒ bounded keys)
    # must cover a saturated peer-sync backoff (30 s envelope): a peer
    # whose connects failed throughout a crash window may legitimately
    # sleep most of that before the reconnect that drains its backlog
    quiesce_timeout_s: float = 90.0
    # memory watermark tolerances vs the post-round-1 baseline
    mem_rss_slack_mb: float = 96.0
    mem_obj_rel_tol: float = 0.10
    mem_obj_abs_tol: int = 50_000
    # warm-start solve-state watermark: the summed
    # Decision.warm_cache_bytes() across nodes (reverse adjacency /
    # pred-DAG aux / host distance mirrors held by cached
    # SolveArtifacts) must stay within this slack of the post-round-1
    # baseline — the enlarged artifact state the topology-delta path
    # retains is exactly the leak class a storm-heavy soak would grow
    # if the idle-trim eviction policy regressed
    warm_cache_slack_mb: float = 32.0
    # prefix-table + nexthop-group-intern watermark: the summed
    # Decision.prefix_table_bytes() across nodes must stay within this
    # slack of the post-round-1 baseline — a churn horizon that leaks
    # withdrawn prefixes into PrefixState, or grows the intern tables
    # without bound, trips here instead of hiding inside total RSS
    # (the million-prefix data plane's leak class; docs/Decision.md)
    prefix_table_slack_mb: float = 24.0
    # device-HBM watermark (monitor/device.py sample_hbm): summed live
    # bytes_in_use across local devices must stay within this slack of
    # the post-round-1 baseline — the leak class where device-resident
    # LSDB table sets, warm distance matrices, or election matrices
    # accumulate in HBM across churn rounds. Skipped (None samples) on
    # backends without memory_stats (CPU), where the RSS watermark
    # already covers the same arrays in host RAM.
    hbm_slack_mb: float = 64.0
    # control knob: build the cluster with messaging bounds DISABLED
    # (caps stay configured, queues unbounded) to prove the watermark
    # checks catch unbounded growth
    enforce_queue_bounds: bool = True


@dataclass
class RoundSample:
    round: int
    rss_mb: float | None
    objects: int
    churn_events: int
    schedule_hash: str
    warm_mb: float = 0.0  # summed Decision warm-start cache footprint
    prefix_mb: float = 0.0  # summed prefix-table + intern-table footprint
    hbm_mb: float | None = None  # summed device bytes_in_use (None on cpu)


@dataclass
class SoakReport:
    seed: int
    rounds: list[RoundSample] = field(default_factory=list)

    def summary(self) -> str:
        lines = [f"soak seed={self.seed}: {len(self.rounds)} round(s) clean"]
        for s in self.rounds:
            rss = f"{s.rss_mb:.0f}MB" if s.rss_mb is not None else "n/a"
            hbm = f"{s.hbm_mb:.0f}MB" if s.hbm_mb is not None else "n/a"
            lines.append(
                f"  round {s.round}: rss={rss} objects={s.objects} "
                f"churn={s.churn_events} warm={s.warm_mb}MB "
                f"prefix={s.prefix_mb}MB hbm={hbm} "
                f"schedule={s.schedule_hash[:12]}"
            )
        return "\n".join(lines)


class PrefixChurner:
    """Sustained background prefix churn through the PrefixManager API
    seam: each step advertises or withdraws one prefix from a fixed
    per-node pool on a seeded-random live node. The pool is fixed so the
    steady-state key count is bounded — what must NOT grow round over
    round is memory, and a drifting advertisement set would mask that.
    """

    def __init__(self, cluster: Cluster, rng, interval_s: float, pool: int):
        self.cluster = cluster
        self.rng = rng
        self.interval_s = interval_s
        self.pool = pool
        self.events = 0
        self._advertised: set[tuple[str, int]] = set()  # (node, idx)
        self._task: asyncio.Task | None = None
        # stable node ids for prefix derivation: crash/restart must not
        # shift another node's churn prefixes onto it
        self._ids = {
            name: i
            for i, name in enumerate(
                sorted(set(cluster.nodes) | set(cluster.crashed))
            )
        }

    def _push(self, node_name: str, idx: int, add: bool) -> None:
        from openr_tpu.prefixmgr.prefix_manager import (
            PrefixEvent,
            PrefixEventType,
            PrefixSource,
        )
        from openr_tpu.types.network import IpPrefix
        from openr_tpu.types.topology import PrefixEntry

        node = self.cluster.nodes.get(node_name)
        if node is None:
            return  # crashed mid-storm: skip this step
        nid = self._ids[node_name] & 0xFF
        entry = PrefixEntry(
            prefix=IpPrefix.make(f"10.200.{nid}.{idx}/32")
        )
        node.prefix_events.push(
            PrefixEvent(
                type=(
                    PrefixEventType.ADD_PREFIXES
                    if add
                    else PrefixEventType.WITHDRAW_PREFIXES
                ),
                source=PrefixSource.API,
                entries=(entry,),
            )
        )
        self.events += 1
        key = (node_name, idx)
        (self._advertised.add if add else self._advertised.discard)(key)

    async def _run(self) -> None:
        while True:
            await asyncio.sleep(self.interval_s)
            names = sorted(self.cluster.nodes)
            if not names:
                continue
            node_name = names[self.rng.randrange(len(names))]
            idx = self.rng.randrange(self.pool)
            add = (node_name, idx) not in self._advertised
            self._push(node_name, idx, add)

    def start(self) -> None:
        assert self._task is None
        # guard: a crash mid-churn must surface (log + counter) the
        # moment it happens, not sit parked on the Task until stop()
        self._task = guard_task(
            asyncio.get_event_loop().create_task(
                self._run(), name="soak.churner"
            ),
            owner="soak.churner",
        )

    async def stop(self, withdraw: bool = True) -> None:
        if self._task is not None:
            # reap swallows only the churner's own cancellation; a
            # cancellation aimed at stop() itself still propagates
            await reap(self._task)
            self._task = None
        if withdraw:
            # return to the base advertisement set so every round
            # quiesces into the same steady state
            for node_name, idx in sorted(self._advertised):
                self._push(node_name, idx, add=False)
            self._advertised.clear()


def _memory_sample() -> tuple[float | None, int]:
    gc.collect()
    return _current_rss_mb(), len(gc.get_objects())


async def run_soak(cfg: SoakConfig) -> SoakReport:
    """Run the multi-round soak; raises :class:`SoakError` (with the
    seed+round replay hint embedded) on any invariant or watermark
    breach."""
    assert cfg.edges, "SoakConfig.edges is required"
    plan = ChaosPlan(
        cfg.seed,
        link_faults=cfg.link_faults,
        kv_faults=cfg.kv_faults,
        fib_faults=cfg.fib_faults,
    )

    def transform(ncfg):
        # an update lost in a storm must fail the round's quiesce check,
        # not be repaired by the periodic full sync and pass a tick later
        ncfg = without_anti_entropy(ncfg)
        if not cfg.enforce_queue_bounds:
            # control case: every node built with bounds OFF while the
            # caps stay configured, so check_queue_bounds still knows
            # the limits
            ncfg = replace(
                ncfg,
                messaging=replace(ncfg.messaging, enforce_bounds=False),
            )
        return ncfg

    cluster = Cluster.from_edges(
        cfg.edges, solver=cfg.solver, chaos=plan,
        node_config_transform=transform,
    )
    # rate faults gate on the per-round storms — initial bring-up is
    # clean so round boundaries always start from a converged baseline
    plan.active = False
    # the work ledger is process-global: clear anything a previous soak
    # or bench left behind so round attribution starts from zero
    work_ledger.reset()
    await cluster.start()
    try:
        await cluster.wait_converged(timeout=cfg.quiesce_timeout_s)
        report = SoakReport(seed=cfg.seed)
        churn_rng = plan.rng("soak/churn")
        baseline: (
            tuple[float | None, int, float, float, float | None] | None
        ) = None
        for rnd in range(cfg.rounds):
            plan.active = True
            cluster.make_storm(
                plan,
                duration_s=cfg.storm_duration_s,
                n_flaps=cfg.n_flaps,
                n_crashes=cfg.n_crashes,
                n_partitions=cfg.n_partitions,
                heal_after_s=cfg.heal_after_s,
                n_disk_faults=cfg.n_disk_faults,
            )
            context = (
                f"soak seed={cfg.seed} round={rnd} "
                f"(--soak --seed {cfg.seed} --rounds {rnd + 1}; "
                f"{plan.replay_hint()})"
            )
            churner = PrefixChurner(
                cluster, churn_rng, cfg.churn_interval_s, cfg.churn_prefixes
            )
            churner.start()
            try:
                await run_schedule(cluster, plan)
            finally:
                await churner.stop(withdraw=True)
            try:
                await wait_quiescent(
                    cluster,
                    timeout_s=cfg.quiesce_timeout_s,
                    context=context,
                )
            except AssertionError as e:
                raise SoakError(str(e)) from e
            # HBM first: on a cpu-oracle soak this is the process's
            # FIRST jax touch, and the import's ~60k live objects must
            # land inside round 0's object-watermark baseline, not be
            # charged to round 1 as a phantom leak
            from openr_tpu.monitor import device as device_telemetry

            hbm_mb = device_telemetry.hbm_in_use_mb()
            rss_mb, objects = _memory_sample()
            warm_mb = (
                sum(
                    n.decision.warm_cache_bytes()
                    for n in cluster.nodes.values()
                )
                / 1e6
            )
            prefix_mb = (
                sum(
                    n.decision.prefix_table_bytes()
                    for n in cluster.nodes.values()
                )
                / 1e6
            )
            report.rounds.append(
                RoundSample(
                    round=rnd,
                    rss_mb=rss_mb,
                    objects=objects,
                    churn_events=churner.events,
                    schedule_hash=plan.schedule_hash(),
                    warm_mb=round(warm_mb, 2),
                    prefix_mb=round(prefix_mb, 2),
                    hbm_mb=None if hbm_mb is None else round(hbm_mb, 2),
                )
            )
            log.info(
                "soak round %d clean: rss=%s objects=%d churn=%d "
                "warm=%.1fMB prefix=%.1fMB hbm=%s",
                rnd, rss_mb, objects, churner.events, warm_mb, prefix_mb,
                hbm_mb,
            )
            if rnd == 0:
                # round 1 is the warmup baseline (JIT caches, interned
                # bytes); monotone growth is judged from here on —
                # and the same boundary arms the work-proportionality
                # invariant (invariants.check_work_ratios): from here
                # every storm round's per-stage touched-entity counts
                # are judged against their deltas
                baseline = (rss_mb, objects, warm_mb, prefix_mb, hbm_mb)
                work_ledger.mark_warm()
                continue
            base_rss, base_obj, base_warm, base_prefix, base_hbm = baseline
            if (
                hbm_mb is not None
                and base_hbm is not None
                and hbm_mb > base_hbm + cfg.hbm_slack_mb
            ):
                raise SoakError(
                    f"device-HBM watermark breach ({context}): "
                    f"{hbm_mb:.1f}MB live device memory > baseline "
                    f"{base_hbm:.1f}MB + {cfg.hbm_slack_mb:.0f}MB slack "
                    "(device-resident tables or warm matrices leaking?)"
                )
            if warm_mb > base_warm + cfg.warm_cache_slack_mb:
                raise SoakError(
                    f"warm-cache watermark breach ({context}): "
                    f"{warm_mb:.1f}MB of warm-start solve state > "
                    f"baseline {base_warm:.1f}MB + "
                    f"{cfg.warm_cache_slack_mb:.0f}MB slack "
                    "(SolveArtifact eviction policy regressed?)"
                )
            if prefix_mb > base_prefix + cfg.prefix_table_slack_mb:
                raise SoakError(
                    f"prefix-table watermark breach ({context}): "
                    f"{prefix_mb:.1f}MB of prefix-table + intern-table "
                    f"state > baseline {base_prefix:.1f}MB + "
                    f"{cfg.prefix_table_slack_mb:.0f}MB slack "
                    "(withdrawn prefixes or nexthop groups leaking?)"
                )
            if (
                rss_mb is not None
                and base_rss is not None
                and rss_mb > base_rss + cfg.mem_rss_slack_mb
            ):
                raise SoakError(
                    f"memory watermark breach ({context}): RSS "
                    f"{rss_mb:.0f}MB > baseline {base_rss:.0f}MB + "
                    f"{cfg.mem_rss_slack_mb:.0f}MB slack"
                )
            obj_cap = base_obj * (1 + cfg.mem_obj_rel_tol) + cfg.mem_obj_abs_tol
            if objects > obj_cap:
                raise SoakError(
                    f"object watermark breach ({context}): "
                    f"{objects} live objects > cap {obj_cap:.0f} "
                    f"(baseline {base_obj})"
                )
        return report
    finally:
        # disarm the process-global proportionality gate so later
        # single-shot assert_invariants calls in the same process
        # (tests) don't inherit this soak's warm window
        work_ledger.reset_warm()
        await cluster.stop()
