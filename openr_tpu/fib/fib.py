"""The Fib module: route-update consumption, diffing, kernel programming.

reference: openr/fib/Fib.cpp † — consumes `DecisionRouteUpdate`s, keeps the
`routeState_` book of programmed routes, programs deltas through the
FibService thrift boundary (openr/platform/NetlinkFibHandler.cpp †),
retries with exponential backoff on failure, runs a periodic full sync,
and republishes *programmed* routes on a stream consumed by PrefixManager
(originate-on-programmed gating) and OpenrCtrl subscribers.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Iterable, Protocol

from openr_tpu.common import constants as C
from openr_tpu.common.backoff import ExponentialBackoff, stable_rng
from openr_tpu.common.eventbase import OpenrModule
from openr_tpu.config import Config
from openr_tpu.messaging import QueueClosedError, ReplicateQueue, RQueue
from openr_tpu.monitor import perf, profiling, work_ledger
from openr_tpu.types.network import IpPrefix, MplsRoute, UnicastRoute
from openr_tpu.types.routes import (
    RibEntry,
    RibMplsEntry,
    RouteUpdate,
    RouteUpdateType,
)
from openr_tpu.types.serde import WireDecodeError, from_wire_bin, to_wire_bin

log = logging.getLogger(__name__)


def _fib_ukey(p: IpPrefix) -> bytes:
    return b"u:" + p.prefix.encode()


def _fib_mkey(label: int) -> bytes:
    return b"m:%d" % label


class FibService(Protocol):
    """The route-programming boundary (reference: Platform.thrift †
    FibService). Implementations: MockFibHandler (tests),
    openr_tpu.platform.NetlinkFibHandler (native), or an RpcClient shim."""

    async def add_unicast_routes(self, client_id: int, routes: list[UnicastRoute]) -> None: ...
    async def delete_unicast_routes(self, client_id: int, prefixes: list[IpPrefix]) -> None: ...
    async def add_mpls_routes(self, client_id: int, routes: list[MplsRoute]) -> None: ...
    async def delete_mpls_routes(self, client_id: int, labels: list[int]) -> None: ...
    async def sync_fib(self, client_id: int, routes: list[UnicastRoute]) -> None: ...
    async def sync_mpls_fib(self, client_id: int, routes: list[MplsRoute]) -> None: ...
    async def get_route_table_by_client(self, client_id: int) -> list[UnicastRoute]: ...
    async def get_mpls_route_table_by_client(self, client_id: int) -> list[MplsRoute]: ...


class FibProgramError(RuntimeError):
    pass


def _dataplane_key_nh(nh) -> tuple:
    """The fields of a nexthop the kernel actually stores — a route
    dumped back from the kernel matches its original on exactly these."""
    act = nh.mpls_action
    labels: tuple = ()
    if act is not None:
        if act.push_labels:
            labels = ("push", tuple(act.push_labels))
        elif act.swap_label is not None:
            labels = ("swap", act.swap_label)
    return (nh.address, nh.if_name, max(1, nh.weight), labels)


def _dataplane_key_unicast(r: UnicastRoute) -> tuple:
    return (r.dest, tuple(sorted(_dataplane_key_nh(n) for n in r.nexthops)))


def _dataplane_key_mpls(r: MplsRoute) -> tuple:
    return (
        r.top_label,
        tuple(sorted(_dataplane_key_nh(n) for n in r.nexthops)),
    )


class MockFibHandler:
    """In-memory FibService with injectable failures.

    reference: MockNetlinkFibHandler in openr/tests/mocks/ † — records
    programmed routes, lets tests fail the next N operations to exercise
    Fib's retry/backoff/sync path, and exposes wait helpers. Beyond the
    count-based `fail_next_n`, `fail_rate` fails each operation with a
    given probability from an injectable RNG — the emulator's chaos
    layer (emulator/chaos.py) drives it from a seeded ChaosPlan so a
    failing soak is replayable."""

    def __init__(self, fail_rate: float = 0.0, rng=None):
        self.unicast: dict[int, dict[IpPrefix, UnicastRoute]] = {}
        self.mpls: dict[int, dict[int, MplsRoute]] = {}
        self.fail_next_n = 0
        self.fail_rate = fail_rate
        self.rng = rng
        self.op_count = 0
        self.fail_count = 0
        self.sync_count = 0
        self._changed = asyncio.Event()

    def _fail_maybe(self):
        self.op_count += 1
        if self.fail_next_n > 0:
            self.fail_next_n -= 1
            self.fail_count += 1
            raise FibProgramError("injected failure")
        if self.fail_rate > 0 and self.rng is not None:
            if self.rng.random() < self.fail_rate:
                self.fail_count += 1
                raise FibProgramError("injected failure (rate)")

    def _notify(self):
        self._changed.set()
        self._changed = asyncio.Event()

    async def wait_for_change(self, timeout: float = 5.0) -> None:
        await asyncio.wait_for(self._changed.wait(), timeout)

    async def add_unicast_routes(self, client_id, routes):
        self._fail_maybe()
        tbl = self.unicast.setdefault(client_id, {})
        for r in routes:
            tbl[r.dest] = r
        self._notify()

    async def delete_unicast_routes(self, client_id, prefixes):
        self._fail_maybe()
        tbl = self.unicast.setdefault(client_id, {})
        for p in prefixes:
            tbl.pop(p, None)
        self._notify()

    async def add_mpls_routes(self, client_id, routes):
        self._fail_maybe()
        tbl = self.mpls.setdefault(client_id, {})
        for r in routes:
            tbl[r.top_label] = r
        self._notify()

    async def delete_mpls_routes(self, client_id, labels):
        self._fail_maybe()
        tbl = self.mpls.setdefault(client_id, {})
        for l in labels:
            tbl.pop(l, None)
        self._notify()

    async def sync_fib(self, client_id, routes):
        self._fail_maybe()
        self.sync_count += 1
        self.unicast[client_id] = {r.dest: r for r in routes}
        self._notify()

    async def sync_mpls_fib(self, client_id, routes):
        self._fail_maybe()
        self.mpls[client_id] = {r.top_label: r for r in routes}
        self._notify()

    async def get_route_table_by_client(self, client_id):
        return list(self.unicast.get(client_id, {}).values())

    async def get_mpls_route_table_by_client(self, client_id):
        return list(self.mpls.get(client_id, {}).values())


# reference: openr/if/Platform.thrift † FibClient enum — OPENR's client id
# namespaces its routes in the FibService against other routing daemons.
# Manual/static routes injected via breeze `fib add` live under their
# own client id so openr's sync_fib (which replaces the WHOLE
# CLIENT_ID_OPENR table) never clobbers them; the netlink backend maps
# each client to its own rtproto for real kernel-side separation.
CLIENT_ID_OPENR = C.FIB_CLIENT_OPENR
CLIENT_ID_STATIC = C.FIB_CLIENT_STATIC


class Fib(OpenrModule):
    """Programs computed routes into the dataplane, reliably.

    State machine mirrors the reference †: AWAITING (no RIB yet) →
    SYNCING (first FULL_SYNC programmed via sync_fib) → SYNCED
    (incremental deltas); any program failure re-enters SYNCING with
    exponential backoff, re-deriving the delta from the route book so no
    update is ever lost.
    """

    # traces awaiting a successful program: bounded like Decision's
    # pending list so a storm can't grow it between retries
    PERF_PENDING_CAP = 64
    BOOK = "fib"  # durable programmed-table book name

    def __init__(
        self,
        config: Config,
        route_updates_reader: RQueue,
        fib_handler: FibService,
        fib_updates_queue: ReplicateQueue | None = None,
        perf_events_queue: ReplicateQueue | None = None,
        counters=None,
        persist=None,
    ):
        super().__init__(f"{config.node_name}.fib", counters=counters)
        self.config = config
        self.handler = fib_handler
        # durable programmed-table book (docs/Persist.md): control-plane
        # form of programmed_*, journaled at the program edges; the
        # warm-boot merge upgrades the kernel dump's routes back to
        # their full control-plane identity when the dataplane
        # projections agree
        self.persist = persist
        self._persist_warm_keys: tuple[set, set] | None = None
        self.reader = route_updates_reader
        self.fib_updates = fib_updates_queue
        self.perf_queue = perf_events_queue
        self._pending_perf: list = []
        self.dry_run = config.node.fib.dry_run
        # the RIB as Decision last gave it to us (desired state)
        self.desired_unicast: dict[IpPrefix, RibEntry] = {}
        self.desired_mpls: dict[int, RibMplsEntry] = {}
        # delta book: the bindings that changed since the last
        # successful program pass. The SYNCED-state program cycle is
        # driven entirely from this book — it never snapshots or
        # re-derives the full desired table, so an idle cycle at a
        # million prefixes is O(1) and a k-route delta is O(k)
        # (invariant: desired == programmed ⊕ pending book; the
        # full-sync/warm-boot paths snapshot desired and clear it)
        self._pend_u_upd: dict[IpPrefix, RibEntry] = {}
        self._pend_u_del: set[IpPrefix] = set()
        self._pend_m_upd: dict[int, RibMplsEntry] = {}
        self._pend_m_del: set[int] = set()
        # handler-call chunking for the batched add/delete path
        self.batch_size = max(1, config.node.fib.program_batch_size)
        # what we have successfully programmed (actual state)
        self.programmed_unicast: dict[IpPrefix, UnicastRoute] = {}
        self.programmed_mpls: dict[int, MplsRoute] = {}
        self.synced = asyncio.Event()  # FIB_SYNCED init gate
        self._need_full_sync = True
        self._have_rib = False  # AWAITING state: no RIB from Decision yet
        self._warm_booted = False  # programmed_* adopted from the kernel
        self._dirty = asyncio.Event()
        self.backoff = ExponentialBackoff(
            config.node.fib.initial_retry_ms,
            config.node.fib.max_retry_ms,
            # a dataplane outage fails every node's programming at once;
            # jitter spreads the retry wave (the envelope, current_ms,
            # stays deterministic for the saturation warning below);
            # name-seeded RNG: decorrelated across nodes, reproducible
            # across runs (seeded-soak replay)
            jitter=True,
            rng=stable_rng(config.node_name, "fib-program"),
        )
        self._fail_streak = 0  # consecutive failed program passes
        self._warned_backoff_saturated = False

    async def main(self) -> None:
        if self.config.node.fib.enable_warm_boot and not self.dry_run:
            # BEFORE consuming any RIB: the dump must reflect the
            # previous incarnation's routes, untouched
            await self._warm_boot()
        self.spawn(self._update_loop(), name=f"{self.name}.updates")
        self.spawn(self._program_loop(), name=f"{self.name}.program")
        self.run_every(
            self.config.node.fib.sync_interval_s,
            self._mark_full_sync,
            name=f"{self.name}.resync",
        )

    async def _warm_boot(self) -> None:
        """Graceful-restart dataplane continuity (reference: Fib
        warm-boot sync †): adopt the kernel's surviving routes as the
        programmed state, so the first RIB programs only the delta and
        forwarding never gaps. The adopted routes lack control-plane-only
        fields (metric, area), so the first-delta comparison uses the
        dataplane projection (_dataplane_key)."""
        try:
            u = await self.handler.get_route_table_by_client(CLIENT_ID_OPENR)
            m = await self.handler.get_mpls_route_table_by_client(
                CLIENT_ID_OPENR
            )
        except asyncio.CancelledError:
            raise  # shutdown during warm boot must propagate (OR005)
        except Exception as exc:  # noqa: BLE001 — cold boot on any failure
            log.info("%s: warm-boot dump unavailable (%s)", self.name, exc)
            return
        if not u and not m:
            return
        # dataplane truth is the dump; the durable book restores the
        # control-plane identity of every route whose dataplane
        # projection survived unchanged (book-only routes are routes
        # the kernel lost — not adopted; dump-only routes are adopted
        # in dump form and reconciled by the one-shot delta below)
        durable_u, durable_m = self._load_durable_routes()
        self.programmed_unicast = {}
        for r in u:
            dr = durable_u.get(r.dest)
            keep = dr is not None and (
                _dataplane_key_unicast(dr) == _dataplane_key_unicast(r)
            )
            self.programmed_unicast[r.dest] = dr if keep else r
        self.programmed_mpls = {}
        for r in m:
            dr = durable_m.get(r.top_label)
            keep = dr is not None and (
                _dataplane_key_mpls(dr) == _dataplane_key_mpls(r)
            )
            self.programmed_mpls[r.top_label] = dr if keep else r
        if self.persist is not None:
            # the `persist_replay` ledger delta baseline: what actually
            # survived, in dataplane-projection form
            self._persist_warm_keys = (
                {_dataplane_key_unicast(r) for r in self.programmed_unicast.values()},  # orlint: disable=OR012,OR013 — one-shot warm-boot baseline, ledgered by persist_replay
                {_dataplane_key_mpls(r) for r in self.programmed_mpls.values()},  # orlint: disable=OR012,OR013 — one-shot warm-boot baseline, ledgered by persist_replay
            )
        self._warm_booted = True
        self._need_full_sync = False  # first program = incremental delta
        if self.counters:
            self.counters.set("fib.warm_boot_routes", len(u) + len(m))
        log.info(
            "%s: warm boot adopted %d unicast / %d mpls routes",
            self.name, len(u), len(m),
        )

    def _load_durable_routes(
        self,
    ) -> tuple[dict[IpPrefix, UnicastRoute], dict[int, MplsRoute]]:
        """Decode the durable programmed-table book; undecodable
        records (schema drift) are dropped loudly, never adopted."""
        durable_u: dict[IpPrefix, UnicastRoute] = {}
        durable_m: dict[int, MplsRoute] = {}
        if self.persist is None:
            return durable_u, durable_m
        for kb, vb in list(self.persist.book(self.BOOK).items()):
            try:
                if kb.startswith(b"u:"):
                    r = from_wire_bin(vb, UnicastRoute)
                    durable_u[r.dest] = r
                elif kb.startswith(b"m:"):
                    r = from_wire_bin(vb, MplsRoute)
                    durable_m[r.top_label] = r
            except WireDecodeError as exc:
                log.warning(
                    "%s: dropping undecodable durable route: %s",
                    self.name, exc,
                )
                self.persist.erase(self.BOOK, kb)
        return durable_u, durable_m

    def _persist_replace(self, desired_u, desired_m) -> None:
        """Full-table program paths: make the durable book equal the
        just-programmed table (replace_book journals only the diff, so
        the resync seam stays delta-proportional on disk)."""
        if self.persist is None:
            return
        mapping = {
            _fib_ukey(p): to_wire_bin(r) for p, r in desired_u.items()
        }
        mapping.update(
            {_fib_mkey(l): to_wire_bin(r) for l, r in desired_m.items()}
        )
        self.persist.replace_book(self.BOOK, mapping)

    def _mark_full_sync(self) -> None:
        self._need_full_sync = True
        self._dirty.set()

    # ------------------------------------------------------------- consume

    async def _update_loop(self) -> None:
        while True:
            try:
                upd = await self.reader.get()
            except QueueClosedError:
                return
            self._fold_update(upd)
            self._have_rib = True
            self._dirty.set()

    def _fold_update(self, upd: RouteUpdate) -> None:
        if upd.perf_events:
            room = self.PERF_PENDING_CAP - len(self._pending_perf)
            self._pending_perf.extend(upd.perf_events[:room])
        if upd.type == RouteUpdateType.FULL_SYNC:
            self.desired_unicast = dict(upd.unicast_to_update)
            self.desired_mpls = dict(upd.mpls_to_update)
            # the full-table program paths snapshot `desired` wholesale,
            # so the delta book is superseded
            self._clear_pending()
            # after a warm boot the incremental diff against the adopted
            # kernel state IS the full sync (it deletes stale routes
            # too) — sync_fib here would defeat dataplane continuity
            if not self._warm_booted:
                self._need_full_sync = True
            return
        for prefix, entry in upd.unicast_to_update.items():
            self.desired_unicast[prefix] = entry
            self._pend_u_upd[prefix] = entry
            self._pend_u_del.discard(prefix)
        for prefix in upd.unicast_to_delete:
            self.desired_unicast.pop(prefix, None)
            self._pend_u_upd.pop(prefix, None)
            self._pend_u_del.add(prefix)
        for label, mentry in upd.mpls_to_update.items():
            self.desired_mpls[label] = mentry
            self._pend_m_upd[label] = mentry
            self._pend_m_del.discard(label)
        for label in upd.mpls_to_delete:
            self.desired_mpls.pop(label, None)
            self._pend_m_upd.pop(label, None)
            self._pend_m_del.add(label)

    def _clear_pending(self) -> None:
        self._pend_u_upd, self._pend_u_del = {}, set()
        self._pend_m_upd, self._pend_m_del = {}, set()

    # ------------------------------------------------------------- program

    async def _program_loop(self) -> None:
        while not self.stopped:
            await self._dirty.wait()
            self._dirty.clear()
            try:
                # traces folded in while _program_once awaits the handler
                # belong to the NEXT pass — only this many were covered
                # by the desired-state snapshot programmed below
                n_covered = len(self._pending_perf)
                # one clock pair: the trace's span and fib.program_ms
                with profiling.annotate("fib:program") as programmed:
                    await self._program_once()
                self.backoff.report_success()
                if self._fail_streak:
                    self._fail_streak = 0
                    self._warned_backoff_saturated = False
                    if self.counters:
                        self.counters.set("fib.program_fail_streak", 0)
                if self._have_rib and not self.synced.is_set():
                    self.synced.set()
                if self.counters:
                    self.counters.increment("fib.program_ok")
                    if self._have_rib:
                        self.counters.add_value(
                            "fib.program_ms", programmed.ms
                        )
                    # refresh work.* gauges at the program edge too —
                    # a fib-only process (no Decision rebuilds) still
                    # exports its ledger view
                    work_ledger.export_to(self.counters)
                self._complete_traces(n_covered)
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001
                self._need_full_sync = True
                self._dirty.set()
                self.backoff.report_error()
                delay = self.backoff.delay_ms / 1e3
                self._fail_streak += 1
                if self.counters:
                    self.counters.increment("fib.program_fail")
                    self.counters.set(
                        "fib.program_fail_streak", self._fail_streak
                    )
                    self.counters.flight_record(
                        "fib.program_fail",
                        streak=self._fail_streak,
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        backoff_ms=round(self.backoff.current_ms, 1),
                    )
                if (
                    self.backoff.current_ms >= self.config.node.fib.max_retry_ms
                    and not self._warned_backoff_saturated
                ):
                    # once per saturation episode: a pinned backoff means
                    # the FibService is persistently failing, not just
                    # riding out transient retry noise
                    self._warned_backoff_saturated = True
                    if self.counters:
                        self.counters.flight_record(
                            "fib.backoff_saturated",
                            streak=self._fail_streak,
                            ms=round(self.backoff.current_ms, 1),
                        )
                    log.warning(
                        "%s: programming backoff saturated at %.0f ms "
                        "after %d consecutive failures — FibService looks "
                        "permanently down",
                        self.name, self.backoff.current_ms, self._fail_streak,
                    )
                log.warning(
                    "%s: programming failed (%s); retry in %.3fs",
                    self.name, exc, delay,
                )
                await asyncio.sleep(delay)

    async def _program_once(self) -> None:
        # AWAITING (reference: Fib waits for the first RIB snapshot before
        # touching the dataplane †): programming an empty FIB before
        # Decision speaks would wipe still-valid warm-boot routes and
        # spuriously pass the FIB_SYNCED gate
        if not self._have_rib:
            return
        if self.dry_run or self._need_full_sync or self._warm_booted:
            await self._program_full_table()
            return
        # ---- delta-native SYNCED path -----------------------------------
        # The cycle is driven by the pending delta book alone: no
        # full-table snapshot, no per-cycle to_unicast_route() of every
        # entry — an idle pass is O(1) and a k-route delta is O(k).
        # Pop the book NOW: folds arriving while we await the handler
        # land in a fresh book and re-trigger via _dirty.
        u_upd, u_del_set = self._pend_u_upd, self._pend_u_del
        m_upd, m_del_set = self._pend_m_upd, self._pend_m_del
        self._clear_pending()
        scanned = len(u_upd) + len(u_del_set) + len(m_upd) + len(m_del_set)
        if self.counters and scanned:
            self.counters.increment("fib.program_scan_routes", scanned)
        if scanned:
            # delta-native cycle touches exactly the popped delta book —
            # work.fib.ratio is pinned at 1 (the ci smoke lane gates it)
            work_ledger.commit("fib", scanned, scanned)
        u_add = []
        for p, e in u_upd.items():
            r = e.to_unicast_route()
            prev = self.programmed_unicast.get(p)
            if prev is not None and prev == r:
                continue  # no-op rebinding (NexthopGroup identity compare)
            u_add.append((p, r))
        u_del = [p for p in u_del_set if p in self.programmed_unicast]
        m_add = []
        for label, me in m_upd.items():
            r = me.to_mpls_route()
            prev = self.programmed_mpls.get(label)
            if prev is not None and prev == r:
                continue
            m_add.append((label, r))
        m_del = [
            label for label in m_del_set if label in self.programmed_mpls
        ]
        if not (u_add or u_del or m_add or m_del):
            return  # idle cycle: no handler traffic, no table walks
        # batched add/delete chunks — one bounded handler call per chunk
        # so a million-route convergence never ships one giant frame
        for lo in range(0, len(u_add), self.batch_size):
            chunk = u_add[lo : lo + self.batch_size]
            await self.handler.add_unicast_routes(
                CLIENT_ID_OPENR, [r for _p, r in chunk]
            )
            self._count_batch(len(chunk))
        for lo in range(0, len(u_del), self.batch_size):
            chunk = u_del[lo : lo + self.batch_size]
            await self.handler.delete_unicast_routes(CLIENT_ID_OPENR, chunk)
            self._count_batch(len(chunk))
        for lo in range(0, len(m_add), self.batch_size):
            chunk = m_add[lo : lo + self.batch_size]
            await self.handler.add_mpls_routes(
                CLIENT_ID_OPENR, [r for _l, r in chunk]
            )
            self._count_batch(len(chunk))
        for lo in range(0, len(m_del), self.batch_size):
            chunk = m_del[lo : lo + self.batch_size]
            await self.handler.delete_mpls_routes(CLIENT_ID_OPENR, chunk)
            self._count_batch(len(chunk))
        for p, r in u_add:
            self.programmed_unicast[p] = r
        for p in u_del:
            self.programmed_unicast.pop(p, None)
        for label, r in m_add:
            self.programmed_mpls[label] = r
        for label in m_del:
            self.programmed_mpls.pop(label, None)
        if self.persist is not None:
            # journal AFTER the handler accepted the delta — the book
            # mirrors programmed state, not intent
            for p, r in u_add:
                self.persist.record(self.BOOK, _fib_ukey(p), to_wire_bin(r))
            for p in u_del:
                self.persist.erase(self.BOOK, _fib_ukey(p))
            for label, r in m_add:
                self.persist.record(
                    self.BOOK, _fib_mkey(label), to_wire_bin(r)
                )
            for label in m_del:
                self.persist.erase(self.BOOK, _fib_mkey(label))
        if self.counters:
            self.counters.increment(
                "fib.routes_programmed",
                len(u_add) + len(u_del) + len(m_add) + len(m_del),
            )
        self._publish_programmed(
            {p: u_upd[p] for p, _r in u_add},
            {label: m_upd[label] for label, _r in m_add},
            u_del=u_del,
            m_del=m_del,
        )

    def _count_batch(self, n: int) -> None:
        if self.counters:
            self.counters.increment("fib.program_batches")
            self.counters.add_value("fib.program_batch_size", n)

    async def _program_full_table(self) -> None:
        """The O(table) program paths: dry-run projection, full resync
        (first RIB / periodic anti-entropy / post-failure recovery), and
        the one-shot warm-boot dataplane-key delta. Each snapshots the
        whole desired table — by design; the SYNCED steady state never
        comes here."""
        # snapshot NOW: _update_loop may fold new updates in while we
        # await the handler, and those must not be reported as
        # programmed (they re-trigger via _dirty). The snapshot covers
        # everything folded so far, so the delta book is superseded —
        # no await sits between the snapshot and the clear.
        snap_u = dict(self.desired_unicast)
        snap_m = dict(self.desired_mpls)
        self._clear_pending()
        # honest O(table) accounting, delta 0: resync/dry-run/warm-boot
        # are full-table by design — recorded under their own stage
        # (the spf_full / merge_full convention) so the delta-native
        # "fib" stage stays gated at ratio 1 while the periodic resync
        # doesn't read as a proportionality breach. With one ledger per
        # PROCESS (the multi-process harness) there is no other node's
        # churn to pool the ratio down, so the split is load-bearing.
        work_ledger.commit("fib_resync", len(snap_u) + len(snap_m), 0)
        desired_u = {p: e.to_unicast_route() for p, e in snap_u.items()}  # orlint: disable=OR012 — full-table resync seam (O(P) by design)
        desired_m = {l: e.to_mpls_route() for l, e in snap_m.items()}
        if self.dry_run:
            self.programmed_unicast = desired_u
            self.programmed_mpls = desired_m
            self._persist_replace(desired_u, desired_m)
            self._publish_programmed(snap_u, snap_m, full=True)
            return
        if self._need_full_sync:
            await self.handler.sync_fib(CLIENT_ID_OPENR, list(desired_u.values()))
            await self.handler.sync_mpls_fib(CLIENT_ID_OPENR, list(desired_m.values()))
            self._need_full_sync = False
            self.programmed_unicast = desired_u
            self.programmed_mpls = desired_m
            self._persist_replace(desired_u, desired_m)
            if self.counters:
                self.counters.increment(
                    "fib.routes_programmed", len(desired_u) + len(desired_m)
                )
            self._publish_programmed(snap_u, snap_m, full=True)
            return
        # warm boot: the programmed side came from a kernel dump, which
        # can't carry control-plane-only fields (metric, area, neighbor
        # name) — this one-shot delta compares the dataplane projection
        # instead, so surviving routes aren't pointlessly reprogrammed.
        def same_u(a: UnicastRoute | None, b: UnicastRoute) -> bool:
            return a is not None and (
                _dataplane_key_unicast(a) == _dataplane_key_unicast(b)
            )

        def same_m(a: MplsRoute | None, b: MplsRoute) -> bool:
            return a is not None and (
                _dataplane_key_mpls(a) == _dataplane_key_mpls(b)
            )

        u_add = [
            r for p, r in desired_u.items()
            if not same_u(self.programmed_unicast.get(p), r)
        ]
        u_del = [p for p in self.programmed_unicast if p not in desired_u]  # orlint: disable=OR012,OR013 — one-shot warm-boot table diff (O(P) by design; accounted by the fib-stage commit above)
        m_add = [
            r for l, r in desired_m.items()
            if not same_m(self.programmed_mpls.get(l), r)
        ]
        m_del = [l for l in self.programmed_mpls if l not in desired_m]  # orlint: disable=OR012,OR013 — one-shot warm-boot table diff; accounted by the fib-stage commit above
        if u_add:
            await self.handler.add_unicast_routes(CLIENT_ID_OPENR, u_add)
        if u_del:
            await self.handler.delete_unicast_routes(CLIENT_ID_OPENR, u_del)
        if m_add:
            await self.handler.add_mpls_routes(CLIENT_ID_OPENR, m_add)
        if m_del:
            await self.handler.delete_mpls_routes(CLIENT_ID_OPENR, m_del)
        # every surviving route is now accounted for in control-plane
        # form; downstream (PrefixManager gating) sees the full state
        self._warm_booted = False
        self.programmed_unicast = desired_u
        self.programmed_mpls = desired_m
        if self._persist_warm_keys is not None:
            # persist_replay accounting (docs/Persist.md): touched =
            # what the boot reconciliation actually shipped to the
            # handler; delta = the genuine desired-vs-durable dataplane
            # difference, derived from the warm-boot adoption baseline
            # — NOT from the add/del lists, so a regression to a full
            # boot-time reprogram inflates touched while delta stays
            # small and the (non-exempt) ledger bound trips.
            du, dm = self._persist_warm_keys
            self._persist_warm_keys = None
            want_u = {_dataplane_key_unicast(r) for r in desired_u.values()}
            want_m = {_dataplane_key_mpls(r) for r in desired_m.values()}
            work_ledger.commit(
                "persist_replay",
                len(u_add) + len(u_del) + len(m_add) + len(m_del),
                len(want_u ^ du) + len(want_m ^ dm),
            )
        self._persist_replace(desired_u, desired_m)
        if self.counters:
            self.counters.set(
                "fib.warm_boot_reprogrammed", len(u_add) + len(m_add)
            )
            work_ledger.export_to(self.counters)
        self._publish_programmed(snap_u, snap_m, full=True)

    def _complete_traces(self, n_covered: int) -> None:
        """Stamp FIB_PROGRAMMED on the first `n_covered` pending traces —
        the ones whose deltas the just-finished program pass actually
        covered — and hand them to Monitor's perf ring. Runs only after
        a SUCCESSFUL _program_once — a failed program keeps the traces
        pending, so the retry latency stays in the trace."""
        if not self._have_rib or not self._pending_perf or n_covered <= 0:
            return
        traces = self._pending_perf[:n_covered]
        self._pending_perf = self._pending_perf[n_covered:]
        for pe in traces:
            pe.add_perf_event(
                perf.FIB_PROGRAMMED, node=self.config.node_name
            )
            if self.perf_queue is not None:
                try:
                    self.perf_queue.push(pe)
                except QueueClosedError:
                    if not self.stopped:
                        raise
                    return
        if self.counters:
            self.counters.increment("fib.perf_traces_completed", len(traces))

    def _publish_programmed(
        self,
        snap_u: dict[IpPrefix, RibEntry],
        snap_m: dict[int, RibMplsEntry],
        full: bool = False,
        u_del: Iterable[IpPrefix] = (),
        m_del: Iterable[int] = (),
    ) -> None:
        """Stream programmed-route updates (reference: Fib's
        fibRouteUpdatesQueue_ †, consumed by PrefixManager gating).
        ``snap_u``/``snap_m`` are the RibEntry bindings actually handed
        to the handler — the whole table on the full paths, ONLY the
        changed bindings on the delta path."""
        if self.fib_updates is None:
            return
        upd = RouteUpdate()
        if full:
            upd.type = RouteUpdateType.FULL_SYNC
            upd.unicast_to_update = dict(snap_u)
            upd.mpls_to_update = dict(snap_m)
        else:
            upd.type = RouteUpdateType.INCREMENTAL
            upd.unicast_to_update = dict(snap_u)
            upd.unicast_to_delete = list(u_del)
            upd.mpls_to_update = dict(snap_m)
            upd.mpls_to_delete = list(m_del)
        self.fib_updates.push(upd)

    # ----------------------------------------------------------- accessors

    def pending_changes(self) -> dict:
        """Desired-vs-programmed delta counts + examples (single source
        of truth for convergence checks — validate uses this instead of
        re-deriving the diff)."""
        desired_u = {p: e.to_unicast_route() for p, e in self.desired_unicast.items()}  # orlint: disable=OR012,OR013 — convergence accessor (validate/invariants), not the program cycle or a ledger stage
        desired_m = {l: e.to_mpls_route() for l, e in self.desired_mpls.items()}  # orlint: disable=OR012,OR013 — convergence accessor
        u_stale = [
            str(p) for p, r in desired_u.items()
            if self.programmed_unicast.get(p) != r
        ]
        u_del = [str(p) for p in self.programmed_unicast if p not in desired_u]  # orlint: disable=OR012,OR013 — convergence accessor
        m_stale = [
            l for l, r in desired_m.items()
            if self.programmed_mpls.get(l) != r
        ]
        m_del = [l for l in self.programmed_mpls if l not in desired_m]  # orlint: disable=OR012,OR013 — convergence accessor
        return {
            "converged": not (u_stale or u_del or m_stale or m_del),
            "desired_unicast": len(desired_u),
            "desired_mpls": len(desired_m),
            "stale": u_stale[:3] + u_del[:3],
            "stale_mpls": m_stale[:3] + m_del[:3],
            "pending": len(u_stale) + len(u_del) + len(m_stale) + len(m_del),
        }

    def get_programmed_unicast(self) -> list[UnicastRoute]:
        return sorted(self.programmed_unicast.values(), key=lambda r: r.dest)

    def get_programmed_mpls(self) -> list[MplsRoute]:
        return sorted(self.programmed_mpls.values(), key=lambda r: r.top_label)
