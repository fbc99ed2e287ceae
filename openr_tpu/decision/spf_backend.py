"""TPU SPF backend: the production route-computation path.

reference: openr/decision/SpfSolver.cpp † — but the solve is the batched
JAX kernel in `openr_tpu.ops.spf_split` instead of per-root scalar
Dijkstra.

The SPF batch for one node's RIB is {self} ∪ neighbors(self): the root row
gives distances, and the neighbor rows give the ECMP first-hop matrix (and,
later, LFA backups) via `first_hop_matrix` — one kernel launch per rebuild,
shapes stable under churn (roots padded to a bucket), so the jit cache stays
warm while topology changes arrive as pure data.

Host-side assembly (prefix loop, NextHop construction) mirrors the
reference's selectBestRoutes/selectBestPathsSpf semantics exactly; the
oracle (`oracle.py`) implements the same semantics on an independent code
path and the test suite asserts RouteDatabase equality between the two.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.common.constants import MPLS_LABEL_MIN
from openr_tpu.decision.election import (
    elect_multi_np,
    iter_multi_winners,
    multi_items,
)
from openr_tpu.decision.ksp import (
    normalize_weights,
    ucmp_weights,
)
from openr_tpu.decision.linkstate import CsrGraph, LinkState, PrefixState
from openr_tpu.decision.oracle import SolveArtifact, metric_key
from openr_tpu.monitor import compile_ledger, profiling, work_ledger
from openr_tpu.monitor import device as device_telemetry
from openr_tpu.types.topology import ForwardingAlgorithm
from openr_tpu.ops.spf import (
    INF_DIST,
    METRIC_MAX,
    pad_batch,
)
from openr_tpu.ops.spf_split import (
    batched_sssp_split,
    batched_sssp_split_rib,
    batched_sssp_split_warm_rib,
    build_split_tables,
    pick_gs_chunks,
    rib_buffer_trailer,
    tight_nodes,
    unpack_rib_buffer,
)
from openr_tpu.types.network import (
    MplsAction,
    MplsActionType,
    NextHop,
    sorted_nexthops,
)
from openr_tpu.types.routes import (
    NexthopIntern,
    RibEntry,
    RibMplsEntry,
    RouteDatabase,
)

log = logging.getLogger(__name__)

# Warm-start cone-scatter pad tiers. pad_batch's power-of-two buckets
# would compile a distinct scatter program per cone-size bucket —
# up to ~17 over a churn run, and a fresh one can land long after
# warmup (the compile ledger's zero-steady-state-recompile gate caught
# exactly this). Three fixed tiers bound the variant count at 3 for the
# whole process while keeping ONE dispatch per warm solve; the padding
# slots repeat the last (row, col) and a duplicate .set of the same
# INF_DIST is a no-op. Cones beyond the top tier chunk by it.
_WARM_SCATTER_TIERS = (8192, 131_072, 1_048_576)


@jax.jit
def _scatter_set(arr, index, values):
    """`arr.at[index].set(values)` as ONE program: `index` (a tuple of
    int32 arrays, one per axis) and `values` (an array of the same
    length, or a scalar) are arguments, so host numpy arrays ride the
    launch and a program is named by arr's shape and dtype, the index
    rank and length, and whether `values` is a scalar — nothing a
    flap changes. The caller hands over no two values for one cell
    (XLA leaves the winner among duplicate indices unspecified) and
    only indices inside `arr`: XLA drops an update that is not, and the
    default mode adds nothing to that, so there is no
    promise_in_bounds to gain.

    Not donated: the warm start's `arr` is the previous artifact's
    distance matrix, which Decision may still hold, and the device
    copy is 0.03 ms — what a patch costs is its dispatch."""
    return arr.at[index].set(values)


#: TpuSpfSolver.last_phase_ms as a view of a compute_routes call's span
#: record: six phases that follow one another and cover the call (only
#: one of the two solve spans runs in a call)
_PHASE_SPANS = {
    "prepare": ("spf:prepare",),
    "solve": ("spf:batched_solve", "spf:native_solve"),
    "unpack": ("spf:unpack",),
    "election": ("spf:rib_election",),
    "assembly": ("spf:rib_unicast",),
    "mpls": ("spf:rib_mpls",),
}


def _warm_scatter_pad(n: int) -> int:
    for t in _WARM_SCATTER_TIERS:
        if n <= t:
            return t
    top = _WARM_SCATTER_TIERS[-1]
    return -(-n // top) * top


def _class_groups(cls_arr: np.ndarray):
    """Index groups of equal values in `cls_arr` (stable order): yields
    int arrays of positions. Shared by the unicast and MPLS class-dict
    sections."""
    if not len(cls_arr):
        return ()
    order = np.argsort(cls_arr, kind="stable")
    bounds = np.nonzero(np.diff(cls_arr[order]))[0] + 1
    return np.split(order, bounds)


def _dest_classes(fh: np.ndarray, d_root: np.ndarray, n_live: int):
    """(class id per live node, content token per class) for the
    (first-hop column, igp) equivalence relation.

    The token is what cross-rebuild caches key on, so it must encode
    the CONTENT (column bits + igp), never the rebuild-local class
    number. Up to 32 neighbor slots + igp packs into one int64 — the
    common case — which makes the unique() a fast 1-D integer sort;
    wider neighbor sets fall back to row-wise unique over bytes.
    """
    packed = np.packbits(fh[:, :n_live], axis=0)  # [P, n_live]
    igp32 = np.ascontiguousarray(d_root[:n_live].astype(np.int32))
    p = packed.shape[0]
    width = p + 4
    key = np.zeros((n_live, 8 if width <= 8 else width), np.uint8)
    key[:, :p] = packed.T
    key[:, p : p + 4] = igp32.view(np.uint8).reshape(n_live, 4)
    if width <= 8:
        flat = key.view(np.int64).ravel()
        tokens, inv = np.unique(flat, return_inverse=True)
        return inv, [int(t) for t in tokens]
    ucls, inv = np.unique(key, axis=0, return_inverse=True)
    return inv, [u.tobytes() for u in ucls]


class _LazyDist:
    """Device-resident [Vp, B] distance matrix, materialized to host only
    on demand.

    The production RIB assembly reads only the root column (supplied
    pre-transferred) and the packed first-hop bits; the full matrix is
    12.8 MB at the 100k benchmark, a device→host copy nothing on that
    path consumes. Consumers that DO want the matrix (LFA backup
    construction, the warm-start cone walk, oracle checks, tests) index
    or np.asarray() this object and pay the transfer once.
    """

    __slots__ = ("_dev", "_d_root", "_np")

    def __init__(self, dev, d_root: np.ndarray):
        self._dev = dev
        self._d_root = d_root
        self._np: np.ndarray | None = None

    @property
    def shape(self):
        return self._dev.shape

    @property
    def dtype(self):
        return np.dtype(np.int32)

    def _materialize(self) -> np.ndarray:
        if self._np is None:
            self._np = np.asarray(self._dev)
            compile_ledger.record_transfer(self._np.nbytes)
        return self._np

    def __array__(self, dtype=None, copy=None):
        a = self._materialize()
        if dtype is not None and np.dtype(dtype) != a.dtype:
            return a.astype(dtype)
        return a

    def __getitem__(self, key):
        # fast path: any spelling of "rows of column 0" ([:, 0],
        # [:n, 0], [:, np.int32(0)]) serves from the pre-transferred
        # root column instead of pulling the full matrix
        if (
            isinstance(key, tuple)
            and len(key) == 2
            and isinstance(key[0], slice)
            and not isinstance(key[1], slice)
            and np.ndim(key[1]) == 0
            and int(key[1]) == 0
        ):
            return self._d_root[key[0]]
        return self._materialize()[key]


class TpuSpfSolver:
    """Computes a node's RouteDatabase on the TPU from the padded CSR LSDB.

    One device kernel family solves: the split-width tables
    (`build_split_tables`, whose base width bounds a hub's padding
    waste by construction) and the kernels of `ops/spf_split.py` over
    them — fused with the first-hop / LFA outputs for one root's RIB,
    plain for a batch of roots, sharded over `mesh` when one is given.
    Beside it, `native_rib` puts the C++ host engine on the single-root
    RIB path. Both give identical routes (tested).
    """

    def __init__(
        self,
        enable_lfa: bool = False,
        ksp_k: int = 2,
        native_rib: str = "auto",
        mesh=None,
        counters=None,
    ):
        # optional per-node Counters registry: annotated solver phases
        # then record wall durations into `profile.<span>_ms` stats
        # (monitor/profiling.py) alongside the xprof timeline rows
        self.counters = counters
        # which device the batched kernels run on, logged once so an
        # operator (and chip_smoke.py) can tell a chip from the CPU
        # backend jax falls back to when it finds no accelerator
        dev0 = jax.devices()[0]
        self.platform = dev0.platform
        self.device_kind = dev0.device_kind
        log.info(
            "TpuSpfSolver: platform=%s device_kind=%s devices=%d "
            "native_rib=%s",
            self.platform, self.device_kind, jax.device_count(), native_rib,
        )
        self.enable_lfa = enable_lfa
        self.ksp_k = ksp_k
        # optional jax.sharding.Mesh (parallel.make_mesh): batched
        # multi-root solves (fleet, all-sources, B=256 shapes) run the
        # sharded split kernel over it — roots over the `sources` axis,
        # table rows over `graph` (parallel/sharded_spf.py). The
        # single-root production rebuild stays single-device: it is a
        # latency shape, and the fused packed-output path wins there.
        self.mesh = mesh
        self._mesh_fallback_warned = False
        # (base_version, node id) → sorted neighbor ids. The CSR's edge
        # STRUCTURE is pinned by base_version (metric churn arrives as
        # overrides, structural change mints a new base), so the O(E)
        # adj_details scan runs once per topology instead of per
        # rebuild; per-solve metrics still read the override-aware
        # csr.details. Small FIFO bound at 4× the device-cache cap
        # (entries are tiny; a steady-state node touches one key).
        self._nbr_cache: dict[tuple[int, int], list[int]] = {}
        # "auto" | "on" | "off": the native C++ radix-heap solver for the
        # single-root RIB path (ops/native_spf.py). auto = use when the
        # shared library is built and LFA is off (LFA needs the batched
        # distance matrix). The batched kernel keeps: LFA, KSP, and
        # all-sources shapes.
        self.native_rib = native_rib
        self._native_cache: dict[int, dict] = {}
        # per-topology-base (out, in) distinct-neighbor counts for the
        # KSP k clamp (_ksp_batch); structural, so metric churn never
        # invalidates it
        self._ksp_nbr_counts: dict[int, tuple] = {}
        # (area, base_version) → int64 node-label vector (MPLS section;
        # labels are structural, see _assemble_routes)
        self._labels_cache: dict[tuple, np.ndarray] = {}
        # device-resident LSDB arrays keyed by the CSR's base version
        # (one entry per area's topology; small LRU): metric-only churn
        # arrives as a patch journal (linkstate.py MetricPatch) and is
        # applied by scatter on device instead of re-uploading O(E)
        # arrays per rebuild (SURVEY §7 step 5: "device-resident LSDB
        # updated by scatter")
        self._dev: dict[int, dict] = {}
        self._dev_lru_cap = 4
        # observability: full table (re)builds+uploads vs in-place patch
        # scatters vs pure hits — under metric-only churn, `uploads`
        # must stay flat after warmup (tested)
        # scatter_calls: _scatter_set programs dispatched (table
        # patches + the warm start's INF scatters), each a host→device
        # round of its own
        # upload_bytes: host bytes `_upload` placed on the device
        self.dev_cache_stats = {
            "uploads": 0, "patches": 0, "hits": 0, "scatter_calls": 0,
            "upload_bytes": 0,
        }
        # observability for the split kernel's regime picks (round-3
        # verdict weak 5: GS chunking must never disable SILENTLY):
        # gs_active / gs_disabled count batched solves by whether dense
        # sweeps ran chunked; uniform_metric counts solves in the
        # hop-count regime (build_split_tables detection — converges in
        # ~diameter sweeps). engine_device / engine_native count solves
        # by the engine that ran them — a batched kernel dispatch on
        # `self.platform`, or the C++ host solver (_use_native) — so a
        # "tpu" backend that never touched the device is visible.
        # Surfaced as decision.spf.* counters.
        # The kernel's own loop counters, read from the packed
        # buffer's trailer (ops.spf_split.rib_buffer_trailer), add up
        # here: dense_sweeps / tail_rounds / net_sweeps / tail_spills /
        # tail_small_rounds of cold solves; the last four warm_-prefixed
        # of warm starts, which run no dense sweep. A spill or a net
        # sweep means the tail's frontier cap was too small;
        # tail_small_rounds well under tail_rounds means frontiers past
        # the small expansion capacity, rounds that sort at tail_cap
        # (docs/Monitor.md "Spans").
        # warm_cone_cells sizes the warm start's host-side cone walk.
        # prewarm_programs: programs prewarm_flap_programs ran (set-up).
        # ksp_jobs / ksp_chunks / ksp_rounds: KSP prefixes handed to
        # _ksp_batch, kernel calls they were cut into, and the rounds
        # (k_eff) those calls were dispatched with, a chunk.
        # ksp_path_nodes: path nodes the decode read (hops + 1 over the
        # paths found), beside k_eff x B x padded_nodes slots fetched.
        # general_prefixes: items that entered _unicast_general, the
        # scalar election; ucmp_prefixes / ucmp_slot_visits: routes
        # _mk_nexthops built with weights, and the (chosen advertiser,
        # first-hop slot) steps of its loop, the weighted election's
        # host work; multi_scoped: anycast prefixes a warm start's
        # advertiser matrix named for re-election; complex_scoped: the
        # same for the complex (scalar fallback) items and their
        # advertiser table, KSP items (always in) not counted.
        # gc_pause_ms / gc_full_collections: the garbage collector's
        # process totals as they stood when the last solver call ended
        # (_note_gc).
        self.spf_kernel_stats = {
            "gs_active": 0, "gs_disabled": 0, "uniform_metric": 0,
            "engine_device": 0, "engine_native": 0,
            "dense_sweeps": 0, "tail_rounds": 0, "net_sweeps": 0,
            "tail_spills": 0, "tail_small_rounds": 0,
            "warm_tail_rounds": 0, "warm_net_sweeps": 0,
            "warm_tail_spills": 0, "warm_tail_small_rounds": 0,
            "warm_cone_cells": 0,
            "prewarm_programs": 0,
            "ksp_jobs": 0, "ksp_chunks": 0, "ksp_rounds": 0,
            "ksp_path_nodes": 0,
            "general_prefixes": 0, "ucmp_prefixes": 0,
            "ucmp_slot_visits": 0, "multi_scoped": 0,
            "complex_scoped": 0,
            "gc_pause_ms": 0.0, "gc_full_collections": 0,
        }
        # what prewarm_flap_programs has run its programs for: one key
        # per (table shapes, batch, has_overloads, gs_chunks), i.e. per
        # set of compiled programs — O(log V) over a process's life
        # (tight_nodes / pad_batch buckets)
        self._prewarmed: set[tuple] = set()
        # SPF engine invocations (kernel launch OR native solve): the
        # dirty-scoped rebuild's acceptance signal — prefix-only churn
        # must leave this flat while routes still update (tested)
        self.solve_count = 0
        # of which: topology-delta warm starts (bounded-region kernel
        # seeded from the cached artifact instead of a cold solve)
        self.warm_solves = 0
        # per-topology-base src-sorted edge index (order, row_start) for
        # the warm start's host-side increase-cone walk; structural, so
        # metric churn never invalidates it (LRU like _dev)
        self._warm_out: dict[int, tuple] = {}
        # cross-rebuild MPLS RibMplsEntry cache: {slot_fingerprint:
        # {(label, node, class_token, igp): RibMplsEntry}} — see the
        # MPLS section of _assemble_routes. LRU over fingerprints; the
        # cap covers one root by default, and compute_fleet_ribs raises
        # it durably to its root count (reclaim via trim_caches())
        self._mpls_cache: dict = {}
        # cross-rebuild unicast RibEntry cache, same fingerprint scheme
        # (see the plain-prefix section of _assemble_routes)
        self._uni_cache: dict = {}
        # class-level {label: RibMplsEntry} sub-dicts (MPLS section)
        self._mpls_cls_cache: dict = {}
        self._mpls_fingerprint_cap = 8
        # nexthop-group intern table (types/routes.NexthopIntern): one
        # shared NexthopGroup object per distinct ECMP set across every
        # route this solver assembles — the million-prefix RIB carries
        # a few thousand of these, and diff/FIB equality collapses to
        # pointer compares on them
        self._nh_intern = NexthopIntern()
        # multi-advertiser election: run the segmented reductions on
        # device (ops/election.py) once the advertiser matrix has at
        # least this many slots; below it the NumPy path wins on
        # dispatch overhead. Byte-equal either way (integer algebra).
        self.elect_device_min = 1 << 15
        # device-resident advertiser matrix per election-view gen
        # (small LRU — one live gen per PrefixState lineage)
        self._elect_dev: dict = {}
        # observability: the last compute_routes call as six phases
        # that follow one another and cover it (prepare, solve, unpack,
        # election, assembly, mpls) — a view of the call's span record
        # (_PHASE_SPANS) — and election shape counts
        self.last_phase_ms: dict[str, float] = {}
        self.elect_stats = {
            "plain": 0, "multi": 0, "complex": 0, "device_elections": 0,
        }
        # per-device shard layout of the last sharded solve's output
        # (monitor/device.shard_rows — metadata only, no device sync);
        # empty until a mesh-sharded solve runs
        self.last_shard_rows: list[dict] = []

    def _device_arrays(self, csr, want: str):
        """Cached (and incrementally patched) device copies of the LSDB.

        `want` selects a table set: "split", the tables every SPF
        solve runs on, or "dense", KSP's tables only (`_ksp_batch`: the
        full-width in-neighbor layout `ops/ksp.py` masks edges in; no
        SPF solve reads it). One cache entry per topology base holds
        the sets asked for so far; metric-only churn patches are
        scattered into each of them, so the KSP tables stay warm under
        churn instead of re-uploading O(E) arrays per rebuild (round-2
        verdict item 4).
        """
        cache = self._dev.get(csr.base_version)
        if cache is not None and csr.version >= cache["version"]:
            # journals are cumulative per base, so patching forward is
            # always correct; a solve against an OLDER snapshot than the
            # cache has applied cannot be patched backward — re-upload
            self._apply_patch_suffix(cache, csr)
        else:
            cache = {
                "version": csr.version,
                "journal_len": len(csr.patches),
                "sets": {},
                "host": {},
            }
        self._dev.pop(csr.base_version, None)  # refresh LRU position
        self._dev[csr.base_version] = cache
        while len(self._dev) > self._dev_lru_cap:
            self._dev.pop(next(iter(self._dev)))
        got = cache["sets"].get(want)
        if got is not None:
            self.dev_cache_stats["hits"] += 1
            return got
        self.dev_cache_stats["uploads"] += 1
        # build the wanted set from the (already journal-complete) csr:
        # the host's table build and the transfer named apart, so that
        # a structural event's time on either can be read
        # (docs/Monitor.md "Spans")
        if want == "split":
            with profiling.annotate("spf:table_build"):
                t = build_split_tables(
                    csr.edge_src, csr.edge_dst, csr.edge_metric,
                    csr.num_nodes,
                )
                vp2 = t["vp"]
                over2 = np.zeros(vp2, dtype=bool)
                m = min(vp2, csr.padded_nodes)
                over2[:m] = csr.node_overloaded[:m]
            dset = {
                "vp": vp2,
                **self._upload({
                    **{k: t[k] for k in (
                        "base_nbr", "base_wgt", "ov_ids", "ov_nbr",
                        "ov_wgt", "out_nbr",
                    )},
                    "over": over2,
                }),
                # host int: hop-count regime marker (0 = mixed metrics);
                # cleared by _apply_patch_suffix when churn breaks it
                "uniform_metric": t["uniform_metric"],
            }
            cache["host"]["split"] = {
                "base_w": t["base_nbr"].shape[1],
                "ov_pos": t["ov_pos"],
            }
        else:
            with profiling.annotate("spf:table_build"):
                nbr, wgt = csr.dense_tables()
            dset = self._upload(
                {"nbr": nbr, "wgt": wgt, "over": csr.node_overloaded}
            )
        cache["sets"][want] = dset
        return dset

    def _upload(self, tables: dict[str, np.ndarray]) -> dict:
        """The host `tables` placed on the device and waited for, under
        `spf:upload`; their bytes land in dev_cache_stats["upload_bytes"].
        The wait is what makes the span the transfer's: without it the
        span would time a dispatch and the transfer would be charged to
        the wall of the kernel that runs next. It costs the event
        nothing: that kernel waits for its tables either way, and a
        base is uploaded once."""
        with profiling.annotate("spf:upload"):
            placed = {k: jnp.asarray(v) for k, v in tables.items()}
            jax.block_until_ready(placed)  # orlint: disable=OR009 — once a topology base, off the steady path; the span has to end with the transfer
        self.dev_cache_stats["upload_bytes"] += sum(
            int(v.nbytes) for v in tables.values()
        )
        return placed

    def _apply_patch_suffix(self, cache, csr) -> None:
        """Scatter the unapplied journal suffix into each set the cache
        holds (the split tables, and KSP's once a KSP prefix has asked
        for them), one compiled scatter (`_set`) per patched array.

        A suffix can name one cell twice with different values (a flap
        fully reverted inside one debounce window solves nothing, so its
        patch waits here for the next flap of the same link): the last
        patch of a cell is the one scattered."""
        if cache["version"] == csr.version:
            return
        done = cache.get("journal_len", 0)
        if len(csr.patches) > done:
            self.dev_cache_stats["patches"] += 1
            with profiling.annotate("spf:patch_scatter"):
                # edge_idx names the cell in both layouts: its dense
                # slot is (dst, edge_idx - row_start[dst])
                last = {p.edge_idx: p for p in csr.patches[done:]}
                # pad the patch arrays to a bucket (repeating the last patch
                # — duplicate .set of the same value is a no-op): without
                # this, every distinct patch COUNT is a new traced shape and
                # the scatter re-compiles on every churn rebuild
                # (~130 ms/cycle measured in round 1)
                patches = list(last.values())
                patches += [patches[-1]] * (
                    pad_batch(len(patches)) - len(patches)
                )
                rows = np.array([p.dense_row for p in patches], np.int32)
                cols = np.array([p.dense_col for p in patches], np.int32)
                vals = np.array([p.metric for p in patches], np.int32)
                for name, dset in cache["sets"].items():
                    if name == "dense":
                        dset["wgt"] = self._set(
                            dset["wgt"], (rows, cols), vals
                        )
                    else:
                        h = cache["host"]["split"]
                        w, ov_pos = h["base_w"], h["ov_pos"]
                        if dset.get("uniform_metric") and bool(
                            (vals != dset["uniform_metric"]).any()
                        ):
                            dset["uniform_metric"] = 0
                        in_base = cols < w
                        if in_base.any():
                            # no-op pad target: repeat the first base patch
                            br = np.where(in_base, rows, rows[in_base][0])
                            bc = np.where(in_base, cols, cols[in_base][0])
                            bv = np.where(in_base, vals, vals[in_base][0])
                            dset["base_wgt"] = self._set(
                                dset["base_wgt"], (br, bc), bv
                            )
                        if (~in_base).any():
                            sel = ~in_base
                            orow = np.where(
                                sel, ov_pos[rows], ov_pos[rows[sel][0]]
                            )
                            ocol = np.where(
                                sel, cols - w, cols[sel][0] - w
                            )
                            ov = np.where(sel, vals, vals[sel][0])
                            dset["ov_wgt"] = self._set(
                                dset["ov_wgt"], (orow, ocol), ov
                            )
            cache["journal_len"] = len(csr.patches)
        cache["version"] = csr.version

    def _set(self, arr, index: tuple, values):
        """One `_scatter_set` program on `arr`'s device, its host
        `index` arrays and `values` (array or scalar) transferred with
        the launch; counted as dev_cache_stats["scatter_calls"]."""
        self.dev_cache_stats["scatter_calls"] += 1
        return _scatter_set(arr, index, values)

    def trim_caches(self, fingerprint_cap: int = 8) -> None:
        """Reclaim assembly-cache memory (e.g. after a fleet pass on a
        shared solver): drop the MPLS fingerprint cap back down and
        evict LRU fingerprints beyond it."""
        self._mpls_fingerprint_cap = fingerprint_cap
        while len(self._mpls_cache) > fingerprint_cap:
            self._mpls_cache.pop(next(iter(self._mpls_cache)))
        while len(self._uni_cache) > fingerprint_cap:
            self._uni_cache.pop(next(iter(self._uni_cache)))
        while len(self._mpls_cls_cache) > fingerprint_cap:
            self._mpls_cls_cache.pop(next(iter(self._mpls_cls_cache)))
        # warm-start host index: cheap to rebuild (one argsort per
        # topology base), so a trim drops it entirely
        self._warm_out.clear()
        # device-resident advertiser matrices: re-uploaded on demand
        self._elect_dev.clear()

    def solve_vp(self, csr) -> int:
        """Node-dimension size of the distance matrix `solve` returns
        (the split tables' tight padding, not the CSR's)."""
        return tight_nodes(csr.num_nodes)

    def _dispatch(self, csr) -> tuple[dict, bool]:
        """Shared dispatch state for every batched-solve entry point:
        (split table set on the device, has_overloads). Every device
        solve — cold, warm, fleet — passes here exactly once, so this
        is where the device engine is counted."""
        self.spf_kernel_stats["engine_device"] += 1
        with profiling.annotate("spf:dispatch"):
            dev = self._device_arrays(csr, "split")
            has_over = bool(csr.node_overloaded.any())
        return dev, has_over

    def _count_kernel_loops(self, buf: np.ndarray, prefix: str = "") -> None:
        """Add the packed buffer's trailer to spf_kernel_stats (`prefix`
        "warm_": the warm kernel, which runs no dense sweep)."""
        got = rib_buffer_trailer(buf)
        st = self.spf_kernel_stats
        if not prefix:
            st["dense_sweeps"] += got["dense_sweeps"]
        st[prefix + "tail_rounds"] += got["tail_rounds"]
        st[prefix + "net_sweeps"] += got["net_sweeps"]
        st[prefix + "tail_spills"] += got["spilled"]
        st[prefix + "tail_small_rounds"] += got["tail_small_rounds"]

    def _solve_dist(self, csr, roots: np.ndarray) -> jax.Array:
        """[vp, B] distances from `roots` (a device array; the call
        returns at the dispatch): the sharded split kernel where a mesh
        is configured and divides the shape, else the single-device
        one."""
        dev, has_over = self._dispatch(csr)
        if self.mesh is not None:
            if self._mesh_fits(dev, roots):
                from openr_tpu.parallel import sharded_sssp_split

                # per-shard span: dispatch wall only (the caller's
                # materialization pays completion); the output's
                # per-device shard layout is kept for ctrl/breeze
                with profiling.annotate(
                    "spf:sharded_solve", counters=self.counters
                ):
                    out = sharded_sssp_split(
                        dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
                        dev["ov_nbr"], dev["ov_wgt"], dev["over"],
                        jnp.asarray(roots), self.mesh,
                        has_overloads=has_over,
                    )
                device_telemetry.observe(
                    "sharded_sssp_split",
                    lambda: sharded_sssp_split.lower(
                        dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
                        dev["ov_nbr"], dev["ov_wgt"], dev["over"],
                        jnp.asarray(roots), self.mesh,
                        has_overloads=has_over,
                    ),
                    span="spf:sharded_solve",
                    # dispatch-only span (async return)
                    span_complete=False,
                )
                self.last_shard_rows = device_telemetry.shard_rows(out)
                return out
            if not self._mesh_fallback_warned:
                self._mesh_fallback_warned = True
                log.warning(
                    "configured mesh %s does not divide solve shape "
                    "(vp=%d, b=%d) — falling back to single-device "
                    "(use power-of-two axis sizes)",
                    dict(self.mesh.shape), dev["vp"], len(roots),
                )
        gs = self._pick_gs_and_count(dev)
        out = batched_sssp_split(
            dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
            dev["ov_nbr"], dev["ov_wgt"], dev["out_nbr"], dev["over"],
            jnp.asarray(roots), has_overloads=has_over, gs_chunks=gs,
        )
        # no span: nothing times this dispatch (the caller's
        # materialization pays completion)
        device_telemetry.observe(
            "batched_sssp_split",
            lambda: batched_sssp_split.lower(
                dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
                dev["ov_nbr"], dev["ov_wgt"], dev["out_nbr"],
                dev["over"], jnp.asarray(roots),
                has_overloads=has_over, gs_chunks=gs,
            ),
        )
        return out

    def _pick_gs_and_count(self, dev: dict) -> int:
        """Gauss-Seidel chunk pick + the regime observability counters
        for a single-device split-table solve (round-3 verdict weak 5:
        chunking must never disable silently)."""
        if dev.get("uniform_metric"):
            self.spf_kernel_stats["uniform_metric"] += 1
        gs = pick_gs_chunks(dev["vp"])
        self.spf_kernel_stats[
            "gs_active" if gs > 1 else "gs_disabled"
        ] += 1
        return gs

    def _mesh_fits(self, dev: dict, roots: np.ndarray) -> bool:
        """Whether this (tables, roots) shape shards evenly over the
        configured mesh — table rows must divide by the graph axis and
        the root batch by the sources axis. tight_nodes pads to
        multiples of 512 and pad_batch to power-of-two buckets, so
        typical meshes (2/4/8 per axis) always fit; anything else falls
        back to the single-device kernel rather than erroring."""
        from openr_tpu.parallel.mesh import GRAPH_AXIS, SOURCES_AXIS

        return (
            dev["vp"] % self.mesh.shape[GRAPH_AXIS] == 0
            and len(roots) % self.mesh.shape[SOURCES_AXIS] == 0
        )

    def _use_native(self) -> bool:
        if self.native_rib == "off":
            return False
        if self.enable_lfa:
            # LFA consumes the batched per-neighbor distance matrix
            return False
        from openr_tpu.ops import native_spf

        if not native_spf.native_available():
            if self.native_rib == "on":
                raise RuntimeError(
                    "native_rib=on but libopenr_spf.so is not built "
                    "(run `make -C native`)"
                )
            return False
        return True

    def _native_out_csr(self, csr):
        """Cached (and patch-forwarded) source-sorted CSR for the native
        solver — same journaling contract as _device_arrays."""
        from openr_tpu.ops.native_spf import OutCsr

        cache = self._native_cache.get(csr.base_version)
        if cache is not None and csr.version >= cache["version"]:
            if cache["version"] != csr.version:
                done = cache["journal_len"]
                for p in csr.patches[done:]:
                    pos = cache["slot_map"][p.edge_idx]
                    if pos >= 0:
                        cache["oc"].w[pos] = p.metric
                cache["journal_len"] = len(csr.patches)
                cache["version"] = csr.version
            return cache["oc"]
        oc, slot_map = OutCsr.from_arrays(
            csr.edge_src, csr.edge_dst, csr.edge_metric, csr.padded_nodes,
            csr.node_overloaded, return_slot_map=True,
        )
        self._native_cache.pop(csr.base_version, None)
        self._native_cache[csr.base_version] = {
            "oc": oc,
            "slot_map": slot_map,
            "version": csr.version,
            "journal_len": len(csr.patches),
        }
        while len(self._native_cache) > self._dev_lru_cap:
            self._native_cache.pop(next(iter(self._native_cache)))
        return oc

    def solve(self, ls: LinkState, my_node: str):
        """Compute distances + the ECMP first-hop matrix for my_node's
        RIB; returns (csr, dist, fh, neighbor_ids, lfa) — lfa is the
        [N, Vp] loop-free-alternate matrix or None when enable_lfa is
        off — or None if my_node is not in the topology. fh/lfa are
        host numpy; dist is host numpy ([Vp, 1], the root's column) from
        the native engine and a `_LazyDist` from the device (root column
        pre-fetched, full [Vp, B] matrix transferred only if
        indexed/np.asarray'd).

        Two interchangeable engines (identical results, tested):
          * native C++ radix-heap Dijkstra + first-hop DAG propagation —
            the single-root latency path (reference runs exactly one
            SPF per root too: openr/decision/SpfSolver.cpp †);
          * the batched TPU kernel ({self} ∪ neighbors roots) with the
            elementwise first-hop identity — the batched/LFA path.
        """
        with profiling.annotate("spf:prepare"):
            with profiling.annotate("spf:to_csr"):
                csr = ls.to_csr()
            my_id = csr.name_to_id.get(my_node)
            if my_id is None:
                return None
            self.solve_count += 1
            nbr_key = (csr.base_version, my_id)
            nbr_ids = self._nbr_cache.get(nbr_key)
            if nbr_ids is None:
                nbr_ids = sorted(
                    d for (s, d) in csr.adj_details if s == my_id
                )
                self._nbr_cache[nbr_key] = nbr_ids
                while len(self._nbr_cache) > 4 * self._dev_lru_cap:
                    self._nbr_cache.pop(next(iter(self._nbr_cache)))
            n = len(nbr_ids)
            b = pad_batch(1 + n)
            nbr_metric_real = self._nbr_metrics(csr, my_id, nbr_ids)
            native = self._use_native()
            if native:
                self.spf_kernel_stats["engine_native"] += 1
                oc = self._native_out_csr(csr)
            else:
                roots, nbr_ids_p, nbr_metric, nbr_over = (
                    self._rib_pad_arrays(
                        csr, my_id, nbr_ids, nbr_metric_real, b
                    )
                )
                dev, has_over = self._dispatch(csr)

        if native:
            with profiling.annotate("spf:native_solve"):
                d1, fh_n = oc.rib_solve(
                    my_id, np.array(nbr_ids, dtype=np.int32),
                    nbr_metric_real,
                )
            with profiling.annotate("spf:unpack"):
                # [Vp, 1]: column 0 = root, like the batch
                dist = d1[:, None]
                fh = np.zeros((b - 1, d1.shape[0]), dtype=bool)
                fh[:n] = fh_n
            return csr, dist, fh, nbr_ids, None

        # fused single-dispatch path with packed outputs: ~0.8 MB
        # instead of ~16 MB of device→host traffic per rebuild at
        # the 100k shape (see ops.spf_split.batched_sssp_split_rib)
        vp = dev["vp"]
        gs = self._pick_gs_and_count(dev)
        with profiling.annotate("spf:batched_solve", counters=self.counters):
            dist_dev, packed = batched_sssp_split_rib(
                dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
                dev["ov_nbr"], dev["ov_wgt"], dev["out_nbr"],
                dev["over"], jnp.asarray(roots),
                jnp.asarray(nbr_metric), jnp.asarray(nbr_ids_p),
                jnp.asarray(nbr_over), jnp.int32(my_id),
                has_overloads=has_over,
                with_lfa=self.enable_lfa,
                gs_chunks=gs,
            )
            buf = np.asarray(packed)
            compile_ledger.record_transfer(buf.nbytes)
        # kernel cost ledger (docs/Monitor.md "Device telemetry"):
        # only re-lowers when the compile ledger saw a fresh compile
        # of this fn — a pure dict probe in steady state
        device_telemetry.observe(
            "batched_sssp_split_rib",
            lambda: batched_sssp_split_rib.lower(
                dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
                dev["ov_nbr"], dev["ov_wgt"], dev["out_nbr"],
                dev["over"], jnp.asarray(roots),
                jnp.asarray(nbr_metric), jnp.asarray(nbr_ids_p),
                jnp.asarray(nbr_over), jnp.int32(my_id),
                has_overloads=has_over,
                with_lfa=self.enable_lfa,
                gs_chunks=gs,
            ),
            span="spf:batched_solve",
        )
        with profiling.annotate("spf:unpack"):
            d_root, fh, lfa = unpack_rib_buffer(
                buf, vp, b, self.enable_lfa
            )
            self._count_kernel_loops(buf)
        return csr, _LazyDist(dist_dev, d_root), fh, nbr_ids, lfa

    @staticmethod
    def _nbr_metrics(csr, my_id: int, nbr_ids: list[int]) -> np.ndarray:
        """metric(root -> neighbor) per neighbor slot: the least over
        parallel adjacencies, with the same METRIC_MAX clamp as the CSR
        builder / oracle, or the first-hop identity breaks for metrics
        above the clamp."""
        out = np.empty(len(nbr_ids), dtype=np.int32)
        for i, d in enumerate(nbr_ids):
            out[i] = min(
                min(det[1] for det in csr.details(my_id, d)), METRIC_MAX
            )
        return out

    def _rib_pad_arrays(
        self, csr, my_id: int, nbr_ids: list[int], nbr_metric_real, b: int
    ):
        """Pad all neighbor-shaped arrays to the same bucket as the
        roots so first_hop_matrix keeps a stable traced shape under
        churn. Padding slots: dead-slot node id, METRIC_MAX metric,
        overloaded=True — can never satisfy the first-hop identity
        (the dead slot is unreachable). Shared by the cold solve and
        the topology-delta warm solve."""
        n = len(nbr_ids)
        dead = self.solve_vp(csr) - 1
        nbr_ids_p = np.full(b - 1, dead, dtype=np.int32)
        nbr_ids_p[:n] = nbr_ids
        nbr_metric = np.full(b - 1, METRIC_MAX, dtype=np.int32)
        nbr_metric[:n] = nbr_metric_real
        nbr_over = np.ones(b - 1, dtype=bool)
        if n:
            nbr_over[:n] = csr.node_overloaded[
                np.array(nbr_ids, dtype=np.int64)
            ]
        roots = np.full(b, my_id, dtype=np.int32)  # padding repeats root
        roots[1 : 1 + n] = nbr_ids
        return roots, nbr_ids_p, nbr_metric, nbr_over

    # ------------------------------------------------------------------ RIB

    def compute_routes(
        self,
        ls: LinkState,
        ps: PrefixState,
        my_node: str,
        return_artifact: bool = False,
    ):
        """Full RIB. With `return_artifact=True`, returns
        (rdb, SolveArtifact | None) — same contract as the oracle's
        `compute_routes`: the artifact wraps the solve() tuple so
        `assemble_prefix_routes` can re-assemble touched prefixes under
        prefix-only churn with zero new kernel launches."""
        rdb = RouteDatabase(this_node_name=my_node)
        # the call's own record (handed on to the caller's, if one is
        # open): last_phase_ms is read from it, not timed a second time
        with profiling.collect() as rec:
            solved = self.solve(ls, my_node)
            if solved is not None:
                with profiling.annotate(
                    "spf:rib_assembly", counters=self.counters
                ):
                    rdb = self._assemble_routes(
                        rdb, ls, ps, my_node, solved
                    )
        ms = rec.ms
        self.last_phase_ms = {
            phase: sum(ms.get(name, 0.0) for name in spans)
            for phase, spans in _PHASE_SPANS.items()
        }
        self._note_gc()
        if solved is None:
            return (rdb, None) if return_artifact else rdb
        if return_artifact:
            return rdb, SolveArtifact(
                my_node=my_node, ls=ls, ksp_k=self.ksp_k, solved=solved
            )
        return rdb

    def _note_gc(self) -> None:
        """The collector's process totals (profiling.gc_totals) into
        spf_kernel_stats at the end of a solver call: cumulative like the
        counts beside them, so a caller that has no Decision around the
        solver reads a window's pauses as a difference."""
        totals = profiling.gc_totals()
        self.spf_kernel_stats["gc_pause_ms"] = totals["pause_ms"]
        self.spf_kernel_stats["gc_full_collections"] = totals[
            "full_collections"
        ]

    def assemble_prefix_routes(
        self, art: SolveArtifact, ps: PrefixState, prefixes
    ) -> dict:
        """Prefix-scoped reassembly against a cached artifact (the
        dirty-scoped rebuild's prefix-only fast path): routes for
        `prefixes` only, re-using the cached solve — no SPF kernel
        launch. Runs every scoped prefix down the general per-prefix
        path (byte-equal to the vectorized plain path — same selection
        semantics, tested); KSP prefixes still batch into one device
        call, which is per-prefix path work, not an SPF solve. A prefix
        absent from the result has no route — the caller deletes it."""
        csr, dist, fh, nbr_ids, lfa = art.solved
        ls, my_node = art.ls, art.my_node
        my_id = csr.name_to_id[my_node]
        d_root = dist[:, 0]
        fh_any = fh.any(axis=0)
        slot_cache = self._nbr_slot_cache(csr, my_id, nbr_ids)
        mk_nexthops_cached = self._mk_nexthops_cached_factory(
            fh, slot_cache, ls.area
        )
        with profiling.annotate("spf:general_items"):
            items = []
            for p in sorted(prefixes):
                per_node = ps.prefixes.get(p)
                if per_node:
                    items.append((p, dict(per_node)))
        # scoped election: candidates examined vs touched prefixes
        work_ledger.commit(
            "election",
            sum(len(pn) for _p, pn in items),
            len(prefixes),
        )
        out: dict = {}
        with profiling.annotate("spf:unicast_general"):
            ksp_jobs = self._unicast_general(
                csr, ls, my_node, my_id, d_root, fh, fh_any, nbr_ids, lfa,
                dist, slot_cache, mk_nexthops_cached, items, out,
            )
        if ksp_jobs:
            self._ksp_batch(csr, ls, my_node, my_id, d_root, ksp_jobs, out)
        return out

    # ------------------------------------------------- topology-delta warm

    def _warm_out_index(self, csr):
        """Src-sorted live-edge permutation + row starts for the warm
        start's host-side increase-cone walk; structural per topology
        base, so metric churn never invalidates it."""
        cached = self._warm_out.get(csr.base_version)
        if cached is None:
            e = csr.num_edges
            src = csr.edge_src[:e].astype(np.int64)
            order = np.argsort(src, kind="stable")
            row_start = np.zeros(csr.padded_nodes + 1, np.int64)
            np.add.at(row_start, src + 1, 1)
            row_start = np.cumsum(row_start)
            cached = (order, row_start)
            self._warm_out[csr.base_version] = cached
            while len(self._warm_out) > self._dev_lru_cap:
                self._warm_out.pop(next(iter(self._warm_out)))
        return cached

    def _warm_cone(
        self, old_csr, old_mat, changes, roots_real, cells_budget
    ):
        """Per-column conservative increase cones (closure of OLD tight
        edges from each raised edge's head — every node whose distance
        can rise is inside; see oracle.warm_spf for the argument).
        Returns (scatter rows, scatter cols, seed mask, union cone) or
        None when the TOTAL cone cells across columns exceed
        `cells_budget` — this walk is host-side Python, so unlike the
        oracle (whose cold solve is Python too) a near-root raise on a
        big uniform-metric fabric could cost far more than the cold
        device solve it replaces; past the budget, falling back to the
        cold kernel is the cheaper move."""
        order, row_start = self._warm_out_index(old_csr)
        dst = old_csr.edge_dst
        met = old_csr.edge_metric  # the PREVIOUS solve's (old) weights
        over = old_csr.node_overloaded
        inf = int(INF_DIST)
        vp, b = old_mat.shape
        seed = np.zeros(vp, bool)
        rows_all: list[int] = []
        cols_all: list[int] = []
        raised = [(u, v, wo) for (u, v, wo, wn) in changes if wn > wo]
        for u, v, wo, wn in changes:
            if wn < wo:
                seed[v] = True  # lowered edge: direct relax target
        cone_union: set[int] = set()
        col0_cone: set[int] = set()
        cells = 0
        for c, r in enumerate(roots_real):
            col = old_mat[:, c]
            cone: set[int] = set()
            stack: list[int] = []
            for u, v, wo in raised:
                du = int(col[u])
                dv = int(col[v])
                if du >= inf or dv >= inf:
                    continue
                if u != r and over[u]:
                    continue  # u never relaxed in this column
                if du + wo == dv and v not in cone:
                    cone.add(v)
                    stack.append(v)
            while stack:
                x = stack.pop()
                if cells + len(cone) > cells_budget:
                    return None
                if x != r and over[x]:
                    continue
                dx = int(col[x])
                for i in order[row_start[x] : row_start[x + 1]]:
                    y = int(dst[i])
                    wo = int(met[i])
                    if wo >= inf:
                        continue
                    dy = int(col[y])
                    if dy < inf and dx + wo == dy and y not in cone:
                        cone.add(y)
                        stack.append(y)
            cells += len(cone)
            for x in cone:
                rows_all.append(x)
                cols_all.append(c)
                seed[x] = True
            if c == 0:
                col0_cone = cone
            cone_union |= cone
        # padding columns are duplicates of column 0 (roots padded by
        # repeating the RIB root): apply its cone so they stay exact
        # upper bounds and converge to the same fixpoint
        for c in range(len(roots_real), b):
            for x in col0_cone:
                rows_all.append(x)
                cols_all.append(c)
        return rows_all, cols_all, seed, cone_union

    def prewarm_flap_programs(self, art: SolveArtifact) -> int:
        """Run, once per set of shapes, every program the next
        metric-only event on `art`'s base can need, so that the event
        itself compiles nothing: `_scatter_set` on `base_wgt`, on
        `ov_wgt` where the base has overflow rows (both at the patch
        bucket one link event pads to), on the [vp, B] distance matrix
        at the first cone tier, and the warm kernel; where the area
        holds a KSP prefix (its base has the dense tables), `_scatter_set`
        on the dense `wgt` too, which every patch is scattered into as
        well, and the KSP kernel at the batch sizes of the area's last
        KSP batch, which every event runs again whole (`_prewarm_ksp`).
        Which of them an
        event meets depends on the link it draws (a raise that is tight
        in some column walks a cone; a patch lands in the overflow
        table only at a node whose in-degree passes the base width), so
        no run of quiet events can promise that the rare ones exist.

        Changes nothing: every result is dropped (`_scatter_set` and
        the kernel donate no argument, so the tables and the artifact's
        distance matrix are the arrays they were), the scatters aim at
        padding that already holds the value they write, and the kernel
        starts from the solved fixpoint with an empty seed mask. No
        stat but `prewarm_programs` moves (`_set` and `_dispatch` are
        not called). Returns the number of programs run: 0 for an
        artifact `warm_compute_routes` would refuse, and for shapes
        already met."""
        if self.enable_lfa or art.solved is None:
            return 0
        csr, dist, _fh, nbr_ids, lfa = art.solved
        cache = self._dev.get(csr.base_version)
        if (
            lfa is not None
            or not isinstance(dist, _LazyDist)
            or cache is None
            or "split" not in cache["sets"]
        ):
            return 0
        dev = cache["sets"]["split"]
        vp = dev["vp"]
        bb = pad_batch(1 + len(nbr_ids))
        has_over = bool(csr.node_overloaded.any())
        gs = pick_gs_chunks(vp)
        tables = (
            "base_nbr", "base_wgt", "ov_ids", "ov_nbr", "ov_wgt",
            "out_nbr", "over",
        )
        # an area with a KSP prefix also holds the dense tables, which
        # every patch is scattered into as well, and re-runs its whole
        # KSP batch on every event
        dense = cache["sets"].get("dense")
        ksp = cache["host"].get("ksp") if dense is not None else None
        key = (
            *(dev[t].shape for t in tables), bb, has_over, gs,
            None if dense is None else dense["wgt"].shape, ksp,
        )
        if key in self._prewarmed:
            return 0
        self._prewarmed.add(key)
        with profiling.annotate("spf:prewarm"):
            dead = vp - 1  # padding row: INF_DIST in every table
            n_patch = pad_batch(1)
            rows = np.full(n_patch, dead, np.int32)
            cols = np.zeros(n_patch, np.int32)
            vals = np.full(n_patch, INF_DIST, np.int32)
            _scatter_set(dev["base_wgt"], (rows, cols), vals)
            programs = 3
            if (cache["host"]["split"]["ov_pos"] >= 0).any():
                _scatter_set(dev["ov_wgt"], (cols, cols), vals)
                programs += 1
            if dense is not None:
                _scatter_set(
                    dense["wgt"],
                    (np.full(n_patch, csr.padded_nodes - 1, np.int32), cols),
                    vals,
                )
                programs += 1
            if ksp is not None:
                programs += self._prewarm_ksp(dense, ksp)
            n_cone = _WARM_SCATTER_TIERS[0]
            _scatter_set(
                dist._dev,
                (np.full(n_cone, dead, np.int32), np.zeros(n_cone, np.int32)),
                INF_DIST,
            )
            my_id = csr.name_to_id[art.my_node]
            roots, nbr_ids_p, nbr_metric, nbr_over = self._rib_pad_arrays(
                csr, my_id, nbr_ids,
                self._nbr_metrics(csr, my_id, nbr_ids), bb,
            )
            args = (
                *(dev[t] for t in tables), jnp.asarray(roots),
                jnp.asarray(nbr_metric), jnp.asarray(nbr_ids_p),
                jnp.asarray(nbr_over), dist._dev,
                jnp.asarray(np.zeros(vp, bool)),
            )
            batched_sssp_split_warm_rib(
                *args, has_overloads=has_over, gs_chunks=gs
            )
            # the kernel's cost row, which the first warm start would
            # otherwise lower and compile for (device.observe)
            device_telemetry.observe(
                "batched_sssp_split_warm_rib",
                lambda: batched_sssp_split_warm_rib.lower(
                    *args, has_overloads=has_over, gs_chunks=gs
                ),
                span="spf:warm_solve",
            )
        self.spf_kernel_stats["prewarm_programs"] += programs
        return programs

    @staticmethod
    def _prewarm_ksp(dense: dict, ksp: tuple) -> int:
        """The KSP kernel at each batch size the area's last KSP batch
        was cut into (`_ksp_batch` noted them), every job's destination
        the root itself: no job starts a walk, so the call ends after its
        first round and bans nothing, whatever the distances and the
        mask hold. Returns the programs run."""
        from openr_tpu.ops.ksp import ksp_edge_disjoint_dense

        my_id, k_eff, max_hops, batches = ksp
        nbr = dense["nbr"]
        for b in batches:
            ksp_edge_disjoint_dense(
                nbr, dense["wgt"], jnp.zeros(nbr.shape, bool),
                jnp.int32(my_id), jnp.full(b, my_id, jnp.int32),
                k=k_eff, max_hops=max_hops,
                dist0=jnp.full(nbr.shape[0], INF_DIST, jnp.int32),
            )
        return len(batches)

    def warm_compute_routes(
        self,
        art: SolveArtifact,
        ls: LinkState,
        ps: PrefixState,
        my_node: str,
        edge_pairs,
        prefix_dirt,
        cached_rdb: RouteDatabase,
        max_frac: float,
    ):
        """Topology-delta warm rebuild for one area on the TPU engine:
        the bounded relaxation kernel re-solves the {self} ∪ neighbors
        batch seeded from the cached solve, then only routes whose
        (distance, first-hop) class actually changed are re-assembled.

        Returns (rdb, new_artifact, touched_prefixes, touched_labels,
        region_nodes) or None to demand a full solve. Fallback
        conditions (None): LFA enabled, native
        single-root artifact (no neighbor distance columns to warm),
        structural CSR base change, root-incident change (my own
        nexthop slot metrics moved), delta or cone exceeding
        `max_frac` of the graph.
        """
        if self.enable_lfa or art.solved is None:
            return None
        old_csr, old_dist, old_fh, nbr_ids, lfa = art.solved
        if lfa is not None or not isinstance(old_dist, _LazyDist):
            return None  # native artifact: no warm columns
        with profiling.annotate("spf:to_csr"):
            csr = ls.to_csr()
        if csr.base_version != old_csr.base_version:
            return None  # structural change: interning/base moved
        my_id = csr.name_to_id.get(my_node)
        if my_id is None:
            return None
        # resolve the dirt pairs against the old/new patched CSR views
        changes: list[tuple[int, int, int, int]] = []
        for u, v in sorted(edge_pairs):
            uid = csr.name_to_id.get(u)
            vid = csr.name_to_id.get(v)
            if uid is None or vid is None:
                return None  # unknown endpoint: not metric-only after all
            if uid == my_id:
                return None  # root-incident
            idx = csr.edge_index.get((uid, vid))
            if idx is None:
                continue  # edge unusable in this base: cannot matter
            w_old = int(old_csr.edge_metric[idx])
            w_new = int(csr.edge_metric[idx])
            if w_old != w_new:
                changes.append((uid, vid, w_old, w_new))
        if len(changes) > max(16, int(max_frac * max(csr.num_edges, 1))):
            return None
        # the cone may legitimately cover most of the graph (a raised
        # edge near the root of a uniform-metric graph) — the fraction
        # caps the delta SET above, not the affected region — but the
        # cone WALK is host Python while the cold solve is a device
        # kernel, so its total cells (cone nodes summed over batch
        # columns) get an absolute budget: generous enough that bench-
        # scale graphs (cells <= B·V ≈ 2.6k at the 320-grid gate) never
        # hit it, small enough that a pathological near-root raise on a
        # 100k fabric (B·V ~ 3.3M interpreted ops) falls back to the
        # ~tens-of-ms cold kernel instead of stalling the rebuild
        cells_budget = max(100_000, 8 * csr.num_nodes)
        b = 1 + len(nbr_ids)
        bb = pad_batch(b)
        touched_labels: set[int] = set()
        if not changes:
            # flap fully reverted inside one window (+ maybe prefix
            # dirt): reuse the solved state, reassemble only the dirt
            solved2 = (csr, old_dist, old_fh, nbr_ids, None)
            art2 = SolveArtifact(
                my_node=my_node, ls=ls, ksp_k=self.ksp_k, solved=solved2
            )
            changed_ids = np.zeros(0, np.int64)
            region = 0
        else:
            with profiling.annotate("spf:dist_mirror"):
                # host mirror of the [vp, B] matrix, fetched once per
                # artifact: every warm start makes a new artifact
                old_mat = np.asarray(old_dist)
            roots_real = [my_id, *nbr_ids]
            with profiling.annotate("spf:warm_cone"):
                cone = self._warm_cone(
                    old_csr, old_mat, changes, roots_real, cells_budget
                )
            if cone is None:
                return None
            rows_all, cols_all, seed, cone_union = cone
            self.spf_kernel_stats["warm_cone_cells"] += len(rows_all)
            dev, has_over = self._dispatch(csr)
            vp = dev["vp"]
            roots, nbr_ids_p, nbr_metric, nbr_over = self._rib_pad_arrays(
                csr, my_id, nbr_ids,
                self._nbr_metrics(csr, my_id, nbr_ids), bb,
            )
            dist_dev = old_dist._dev
            with profiling.annotate("spf:warm_scatter"):
                if rows_all:
                    n_sc = len(rows_all)
                    nb = _warm_scatter_pad(n_sc)
                    rows = np.full(nb, rows_all[-1], np.int32)
                    rows[:n_sc] = rows_all
                    cols = np.full(nb, cols_all[-1], np.int32)
                    cols[:n_sc] = cols_all
                    top = _WARM_SCATTER_TIERS[-1]
                    for off in range(0, nb, top):
                        dist_dev = self._set(
                            dist_dev,
                            (rows[off : off + top], cols[off : off + top]),
                            INF_DIST,
                        )
            gs = pick_gs_chunks(vp)
            with profiling.annotate("spf:warm_solve", counters=self.counters):
                dist_dev2, packed = batched_sssp_split_warm_rib(
                    dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
                    dev["ov_nbr"], dev["ov_wgt"], dev["out_nbr"],
                    dev["over"], jnp.asarray(roots),
                    jnp.asarray(nbr_metric), jnp.asarray(nbr_ids_p),
                    jnp.asarray(nbr_over),
                    dist_dev, jnp.asarray(seed),
                    has_overloads=has_over, gs_chunks=gs,
                )
                buf = np.asarray(packed)
                compile_ledger.record_transfer(buf.nbytes)
            device_telemetry.observe(
                "batched_sssp_split_warm_rib",
                lambda: batched_sssp_split_warm_rib.lower(
                    dev["base_nbr"], dev["base_wgt"], dev["ov_ids"],
                    dev["ov_nbr"], dev["ov_wgt"], dev["out_nbr"],
                    dev["over"], jnp.asarray(roots),
                    jnp.asarray(nbr_metric), jnp.asarray(nbr_ids_p),
                    jnp.asarray(nbr_over),
                    dist_dev, jnp.asarray(seed),
                    has_overloads=has_over, gs_chunks=gs,
                ),
                span="spf:warm_solve",
            )
            with profiling.annotate("spf:warm_unpack"):
                d_root, fh, _ = unpack_rib_buffer(buf, vp, bb, False)
                self._count_kernel_loops(buf, "warm_")
                self.solve_count += 1
                self.warm_solves += 1
                n_live = len(csr.node_names)
                old_d_root = old_dist._d_root
                changed = (
                    d_root[:n_live] != old_d_root[:n_live]
                ) | (fh[:, :n_live] != old_fh[:, :n_live]).any(axis=0)
                changed_ids = np.nonzero(changed)[0]
                region = len(cone_union | set(changed_ids.tolist()))
            solved2 = (csr, _LazyDist(dist_dev2, d_root), fh, nbr_ids, None)
            art2 = SolveArtifact(
                my_node=my_node, ls=ls, ksp_k=self.ksp_k, solved=solved2
            )

        # ---- scoped reassembly ---------------------------------------
        with profiling.annotate("spf:warm_reassemble"):
            with profiling.annotate("spf:warm_scope"):
                _c2, dist2, fh2, _n2, _l2 = art2.solved
                d_root2 = dist2[:, 0]
                n_live = len(csr.node_names)
                changed_mask = np.zeros(csr.padded_nodes, bool)
                changed_mask[changed_ids] = True
                view = ps.election_view(csr.name_to_id, csr.base_version)
                touched = set(prefix_dirt)
                if len(view.plain_p):
                    for i in np.nonzero(changed_mask[view.orig])[0]:
                        touched.add(view.plain_p[int(i)])
                if view.multi is not None:
                    # anycast ECMP: the election outcome depends only on
                    # its advertisers' (dist, first-hop) classes — scope by
                    # the advertiser matrix instead of re-assembling all
                    t = view.multi
                    hit = t.known & changed_mask[t.adv]
                    scoped = np.unique(t.seg[hit]).tolist()
                    self.spf_kernel_stats["multi_scoped"] += len(scoped)
                    for i in scoped:
                        touched.add(t.prefixes[i])
                # UCMP/constrained/mixed prefixes: the scalar election
                # reads its advertisers' (dist, first-hop) classes and
                # nothing else of the solve, so the advertiser table
                # scopes them as the matrix above scopes anycast. Only
                # KSP depends on the whole graph: those items are always
                # re-assembled (exact)
                ct = view.complex_table
                scoped = np.setdiff1d(
                    ct.seg[changed_mask[ct.adv]], ct.whole_graph
                )  # sorted, unique
                self.spf_kernel_stats["complex_scoped"] += len(scoped)
                for i in np.concatenate((scoped, ct.whole_graph)).tolist():
                    touched.add(view.complex_items[i][0])
            entries = self.assemble_prefix_routes(art2, ps, touched)
            with profiling.annotate("spf:warm_table_copy"):
                rdb = RouteDatabase(this_node_name=my_node)
                rdb.unicast_routes = dict(cached_rdb.unicast_routes)
                rdb.mpls_routes = dict(cached_rdb.mpls_routes)
                for p in touched:
                    e = entries.get(p)
                    if e is None:
                        rdb.unicast_routes.pop(p, None)
                    else:
                        rdb.unicast_routes[p] = e
            with profiling.annotate("spf:warm_labels"):
                if len(changed_ids):
                    labels_v = self._node_labels(ls, csr, n_live)
                    slot_cache = self._nbr_slot_cache(csr, my_id, nbr_ids)
                    mk = self._mk_nexthops_cached_factory(
                        fh2, slot_cache, ls.area
                    )
                    for i in changed_ids.tolist():
                        if i == my_id:
                            continue
                        label = int(labels_v[i])
                        if label < MPLS_LABEL_MIN:
                            continue
                        touched_labels.add(label)
                        node = csr.node_names[i]
                        if d_root2[i] >= INF_DIST or not fh2[:, i].any():
                            rdb.mpls_routes.pop(label, None)
                            continue
                        igp = int(d_root2[i])
                        nhs = self._mpls_wrap(
                            mk(np.array([i]), igp), node, label
                        )
                        if nhs:
                            rdb.mpls_routes[label] = RibMplsEntry(
                                label=label, nexthops=nhs
                            )
                        else:
                            rdb.mpls_routes.pop(label, None)
        self._note_gc()
        return rdb, art2, touched, touched_labels, region

    def _assemble_routes(self, rdb, ls, ps, my_node, solved):
        with profiling.annotate("spf:rib_election"):
            csr, dist, fh, nbr_ids, lfa = solved
            my_id = csr.name_to_id[my_node]
            d_root = dist[:, 0]  # [Vp]
            # hoisted out of the per-prefix loop: "does ANY neighbor serve as
            # a first hop toward node X" is O(B) per node — scanning it per
            # prefix made RIB assembly O(P·B·V) and dominated churn rebuilds
            fh_any = fh.any(axis=0)  # [Vp]
            slot_cache = self._nbr_slot_cache(csr, my_id, nbr_ids)
            mk_nexthops_cached = self._mk_nexthops_cached_factory(
                fh, slot_cache, ls.area
            )

            # per-destination-node (first-hop column, igp) equivalence
            # classes, computed ONCE and shared by the plain-prefix and MPLS
            # sections: dest_cls[i] is node i's class, dest_tokens[c] a
            # content-stable hashable token (survives rebuilds — it encodes
            # the column bits + igp, so cross-rebuild caches can key on it)
            n_live = len(csr.node_names)
            dest_cls, dest_tokens = _dest_classes(fh, d_root, n_live)

            # ---- unicast: plain prefixes, vectorized ----------------------
            # The dominant RIB shape is "one advertiser, SP_ECMP, no
            # constraints" (every loopback in the fabric). PrefixState
            # pre-classifies those (cached across churn), and their routes
            # assemble here in bulk: reachability/IGP as numpy vectors, and
            # NextHop construction deduplicated by unique (first-hop-column,
            # igp) classes — in a fat-tree thousands of prefixes collapse to
            # a handful of classes. The general per-prefix loop below keeps
            # every other case (anycast, UCMP, KSP, min_nexthop, LFA).
            view = ps.election_view(csr.name_to_id, csr.base_version)
            plain_p, plain_n = view.plain_p, view.plain_n
            plain_e, orig = view.plain_e, view.orig
            complex_items, view_gen = view.complex_items, view.gen
            multi = view.multi
            if lfa is not None:
                # LFA backups are per-target, not per-class — every prefix
                # takes the general scalar loop when LFA is enabled (the
                # fallback matrix in docs/Decision.md)
                merged = list(complex_items)
                if len(plain_p):
                    merged += [
                        (p, {plain_n[i]: plain_e[i]})
                        for i, p in enumerate(plain_p)
                    ]
                if multi is not None:
                    merged += multi_items(multi)
                complex_items = sorted(merged)
                multi = None
                plain_p = []
            self.elect_stats["plain"] = len(plain_p)
            self.elect_stats["multi"] = (
                len(multi.prefixes) if multi is not None else 0
            )
            self.elect_stats["complex"] = len(complex_items)
            # work ledger election stage (full solve): delta = electable
            # prefixes, touched = candidate advertiser slots — the ratio is
            # the mean advertisers-per-prefix, bounded by topology fanout
            n_elect = (
                len(plain_p)
                + self.elect_stats["multi"]
                + len(complex_items)
            )
            work_ledger.commit(
                "election",
                len(plain_p)
                + (len(multi.adv) if multi is not None else 0)
                + sum(len(pn) for _p, pn in complex_items),
                n_elect,
            )
            # multi-advertiser election: the masked argmax/argmin over the
            # prefix→advertiser matrix (device-side segmented reductions
            # past elect_device_min slots, NumPy below — byte-equal)
            mel = None
            if multi is not None and len(multi.prefixes):
                mel = self._elect_multi(multi, d_root, fh_any, my_id, view_gen)
            # fingerprint for every cross-rebuild assembly cache: my own
            # adjacency slot details (interface names, min-metric parallel
            # links), which the fh column alone can't see
            slot_gen = (ls.area, tuple(tuple(s) for s in slot_cache))
            if len(plain_p):
                reach = (
                    (d_root[orig] < INF_DIST) & fh_any[orig] & (orig != my_id)
                )
                igp = d_root[orig].astype(np.int64)
                idxs = np.nonzero(reach)[0]
                cls = dest_cls[orig[idxs]]  # shared per-node classification
                ucls, uidx = np.unique(cls, return_index=True)
                class_nhs = {}
                for c, u in zip(ucls.tolist(), uidx.tolist()):
                    i = idxs[u]
                    class_nhs[c] = self._mk_nexthops_union(
                        slot_cache, fh[:, orig[i]], int(igp[i]), ls.area
                    )
        with profiling.annotate("spf:rib_unicast"):
            cell = None
            if len(plain_p) or mel is not None:
                # cross-rebuild RibEntry caches (same shape as the MPLS
                # entry cache below): under churn most plain prefixes keep
                # the same (first-hop set, igp) class, and the frozen
                # RibEntry can be reused as-is — which also lets the
                # Decision/Fib diffs skip field-by-field equality via
                # identity. Three levels, all scoped to the slot fingerprint
                # and the solver_view generation:
                #   entries:    (view row, class token) → RibEntry
                #   classdicts: (token, membership fp) → {prefix: RibEntry}
                #   plain/multi: content signature → the WHOLE assembled
                #                dict of the section — a steady-state
                #                rebuild whose election outcome is
                #                byte-identical re-lands the section as one
                #                C-speed dict.update, no per-class loop
                cell = self._uni_cache.pop(slot_gen, None)
                if cell is None or cell.get("gen") != view_gen:
                    cell = {"gen": view_gen, "entries": {}, "classdicts": {}}
                self._uni_cache[slot_gen] = cell
                while len(self._uni_cache) > self._mpls_fingerprint_cap:
                    self._uni_cache.pop(next(iter(self._uni_cache)))
            if len(plain_p):
                entries = cell["entries"]
                classdicts = cell["classdicts"]
                if len(entries) > max(8192, 4 * len(plain_p)):
                    entries.clear()
                    classdicts.clear()
                    cell.pop("plain", None)
                    cell["cd_total"] = 0
                # content signature of this rebuild's entire plain section:
                # membership rows + their class ids + the CONTENT tokens of
                # every used class (tokens encode first-hop bits + igp, and
                # the gen guard pins the view arrays the rows index)
                sig = (
                    idxs.tobytes(),
                    cls.tobytes(),
                    tuple(dest_tokens[int(c)] for c in ucls),
                )
                cached_plain = cell.get("plain")
                unicast = rdb.unicast_routes
                if cached_plain is not None and cached_plain[0] == sig:
                    unicast.update(cached_plain[1])
                else:
                    plain_dict: dict = {}
                    for g in _class_groups(cls):
                        c = int(cls[g[0]])
                        nhs = class_nhs[c]
                        if not nhs:
                            continue
                        rows = idxs[g]
                        token = dest_tokens[c]
                        # membership keyed by the BYTES (not their hash): a
                        # 64-bit hash collision would silently install
                        # another class's routes — unacceptable for a RIB
                        gkey = (token, rows.tobytes())
                        sub = classdicts.get(gkey)
                        if sub is None:
                            sub = {}
                            igp_c = int(igp[rows[0]])
                            for i in rows.tolist():
                                key = (i, token)
                                e = entries.get(key)
                                if e is None:
                                    p = plain_p[i]
                                    e = RibEntry(
                                        prefix=p,
                                        nexthops=nhs,
                                        best_node=plain_n[i],
                                        best_nodes=(plain_n[i],),
                                        best_entry=plain_e[i],
                                        igp_cost=igp_c,
                                    )
                                    entries[key] = e
                                sub[e.prefix] = e
                            # bound by TOTAL cached route objects, not key
                            # count: under churn every rebuild mints new
                            # tokens and each stale key pins a whole sub-dict
                            cell["cd_total"] = (
                                cell.get("cd_total", 0) + len(sub)
                            )
                            if cell["cd_total"] > 4 * max(len(plain_p), 4096):
                                classdicts.clear()
                                cell["cd_total"] = len(sub)
                            classdicts[gkey] = sub
                        plain_dict.update(sub)
                    cell["plain"] = (sig, plain_dict)
                    unicast.update(plain_dict)

            # ---- unicast: elected multi-advertiser (anycast ECMP) --------
            # entry construction per surviving prefix; the nexthop union is
            # per chosen SET via the memoized factory, so thousands of
            # anycast prefixes to the same originator set share one group —
            # and an unchanged election outcome (signature over the
            # chosen/best masks + igp vector) re-lands last rebuild's
            # entry dict wholesale, preserving identity for the diff
            if mel is not None:
                # the signature must cover the NEXTHOP inputs too, not just
                # the election outcome: a remote metric change can drop one
                # of two equal-cost paths without moving d_root or the
                # chosen set (review finding) — the advertisers' first-hop
                # columns are gathered into the signature so stale groups
                # can never be re-landed
                sig_m = (
                    mel.is_best.tobytes(),
                    mel.chosen.tobytes(),
                    mel.min_igp.tobytes(),
                    fh[:, multi.adv].tobytes(),
                )
                cached_m = cell.get("multi")
                if cached_m is not None and cached_m[0] == sig_m:
                    rdb.unicast_routes.update(cached_m[1])
                else:
                    mdict: dict = {}
                    for (
                        p, best_names, chosen_ids, chosen_names, igp_c, best_e
                    ) in iter_multi_winners(multi, mel):
                        nhs = mk_nexthops_cached(chosen_ids, igp_c)
                        if not nhs:
                            continue
                        mdict[p] = RibEntry(
                            prefix=p,
                            nexthops=nhs,
                            best_node=chosen_names[0],
                            best_nodes=best_names,
                            best_entry=best_e,
                            igp_cost=igp_c,
                        )
                    cell["multi"] = (sig_m, mdict)
                    rdb.unicast_routes.update(mdict)

            # ---- unicast: general path -----------------------------------
            with profiling.annotate("spf:unicast_general"):
                ksp_jobs = self._unicast_general(
                    csr, ls, my_node, my_id, d_root, fh, fh_any, nbr_ids,
                    lfa, dist, slot_cache, mk_nexthops_cached, complex_items,
                    rdb.unicast_routes,
                )
            if ksp_jobs:
                self._ksp_batch(
                    csr, ls, my_node, my_id, d_root, ksp_jobs,
                    rdb.unicast_routes,
                )

        with profiling.annotate("spf:rib_mpls"):

            # ---- MPLS node segments --------------------------------------
            # cross-rebuild cache: under churn most nodes keep the same
            # (first-hop set, igp), so the per-node SWAP/PHP NextHop
            # construction — the single hottest host loop in a steady-state
            # rebuild — is skipped for every unchanged destination. Keyed by
            # the shared `slot_gen` fingerprint computed above.
            # re-insert to refresh the fingerprint's LRU position
            mpls_cache = self._mpls_cache.pop(slot_gen, None) or {}
            self._mpls_cache[slot_gen] = mpls_cache
            # evict least-recently-used fingerprints (NOT a full wipe — the
            # fleet path serves many roots per pass, each a fingerprint, and
            # a wipe would defeat the cross-rebuild cache it relies on); the
            # cap is raised by compute_fleet_ribs to cover its root count
            while len(self._mpls_cache) > self._mpls_fingerprint_cap:
                self._mpls_cache.pop(next(iter(self._mpls_cache)))
            if len(mpls_cache) > max(4096, 4 * len(csr.node_names)):
                mpls_cache.clear()
            # vectorized per-destination eligibility; the expensive content
            # key reuses the shared dest_cls/dest_tokens classification, so
            # the steady-state loop is token-keyed dict hits (no per-node
            # tobytes/hashing of columns)
            names = csr.node_names
            ids = np.arange(n_live, dtype=np.int64)
            labels_v = self._node_labels(ls, csr, n_live)
            elig = (
                (labels_v >= MPLS_LABEL_MIN)
                & (ids != my_id)
                & (d_root[:n_live] < INF_DIST)
                & fh_any[:n_live]
            )
            sel = np.nonzero(elig)[0]
            mpls_routes = rdb.mpls_routes
            # class-level sub-dict reuse, mirroring the unicast path: a
            # destination class whose membership, labels, and (fh, igp)
            # token are unchanged since a previous rebuild is ONE dict
            # update. base_version is in the key because rows are node IDS
            # (the name↔id interning changes with the topology base).
            mcell = self._mpls_cls_cache.pop(slot_gen, None) or {
                "groups": {}, "total": 0
            }
            self._mpls_cls_cache[slot_gen] = mcell
            while len(self._mpls_cls_cache) > self._mpls_fingerprint_cap:
                self._mpls_cls_cache.pop(next(iter(self._mpls_cls_cache)))
            mcls = mcell["groups"]
            cls_sel = dest_cls[sel]
            for g in _class_groups(cls_sel):
                rows = sel[g]
                token = dest_tokens[int(cls_sel[g[0]])]
                lab = labels_v[rows]
                # bytes, not hashes, for the same reason as the unicast path
                gkey = (csr.base_version, token, rows.tobytes(), lab.tobytes())
                sub = mcls.get(gkey)
                if sub is None:
                    sub = {}
                    igp = int(d_root[rows[0]])
                    for i in rows.tolist():
                        node = names[i]
                        label = int(labels_v[i])
                        key = (label, node, token, igp)
                        entry = mpls_cache.get(key)
                        if entry is None:
                            nhs = self._mpls_wrap(
                                mk_nexthops_cached(np.array([i]), igp),
                                node, label,
                            )
                            if not nhs:
                                continue
                            entry = RibMplsEntry(label=label, nexthops=nhs)
                            mpls_cache[key] = entry
                        sub[label] = entry
                    mcell["total"] += len(sub)
                    if mcell["total"] > 4 * max(n_live, 4096):
                        mcls.clear()
                        mcell["total"] = len(sub)
                    mcls[gkey] = sub
                mpls_routes.update(sub)

            # ---- MPLS adjacency labels -----------------------------------
            my_db = ls.adjacency_db(my_node)
            if my_db:
                for a in my_db.adjacencies:
                    if a.adj_label < MPLS_LABEL_MIN:
                        continue
                    if (
                        a.other_node_name not in csr.name_to_id
                        or a.is_overloaded
                    ):
                        continue
                    if ls.link_drained_by_peer(my_node, a):
                        continue  # far side soft-drained the link
                    rdb.mpls_routes[a.adj_label] = RibMplsEntry(
                        label=a.adj_label,
                        nexthops=(
                            NextHop(
                                address=a.other_node_name,
                                if_name=a.if_name,
                                metric=int(a.metric),
                                neighbor_node=a.other_node_name,
                                area=ls.area,
                                mpls_action=MplsAction(
                                    action=MplsActionType.PHP
                                ),
                            ),
                        ),
                    )
        return rdb

    def _elect_multi(self, multi, d_root, fh_any, my_id, view_gen):
        """Multi-advertiser election dispatch: device-side segmented
        reductions (ops/election.py) once the advertiser matrix is big
        enough to amortize a dispatch, NumPy below. Integer algebra —
        the two produce identical results (tested)."""
        reach = (np.asarray(d_root) < INF_DIST) & fh_any
        if len(multi.adv) >= self.elect_device_min:
            from openr_tpu.ops.election import elect_multi_device

            self.elect_stats["device_elections"] += 1
            self._elect_dev.pop(view_gen, None)  # refresh LRU position
            with profiling.annotate(
                "spf:election", counters=self.counters
            ):
                out = elect_multi_device(
                    multi, np.asarray(d_root), reach, my_id,
                    dev_cache=self._elect_dev, gen=view_gen,
                )
            while len(self._elect_dev) > self._dev_lru_cap:
                self._elect_dev.pop(next(iter(self._elect_dev)))
            return out
        return elect_multi_np(
            multi, np.asarray(d_root).astype(np.int64), reach, my_id
        )

    @staticmethod
    def _mpls_wrap(base, node: str, label: int) -> tuple[NextHop, ...]:
        """Wrap a node-segment target's base nexthops with the SWAP/PHP
        MPLS actions (reference: createMplsRoutes † — PHP when the
        nexthop IS the target). The single source of the construction
        for BOTH the full assembly and the topology-delta scoped
        reassembly, so warm/full byte-parity holds by shared code."""
        return tuple(
            NextHop(
                address=nh.address,
                if_name=nh.if_name,
                metric=nh.metric,
                neighbor_node=nh.neighbor_node,
                area=nh.area,
                mpls_action=(
                    MplsAction(action=MplsActionType.PHP)
                    if nh.neighbor_node == node
                    else MplsAction(
                        action=MplsActionType.SWAP, swap_label=label
                    )
                ),
            )
            for nh in base
        )

    def _node_labels(self, ls: LinkState, csr, n_live: int) -> np.ndarray:
        """Per-node MPLS label vector, cached per topology base: a
        node_label change is structural in _metric_only_delta (full CSR
        rebuild → new base_version), so the O(V) python label scan —
        measured 57 ms of a warm 100k rebuild (r5 profile) — runs once
        per base. Shared by the full assembly and the topology-delta
        scoped MPLS reassembly."""
        labels_v = self._labels_cache.get((ls.area, csr.base_version))
        if labels_v is None:
            labels_v = np.fromiter(
                (ls.node_label(nm) for nm in csr.node_names), np.int64,
                count=n_live,
            )
            self._labels_cache[(ls.area, csr.base_version)] = labels_v
            while len(self._labels_cache) > self._dev_lru_cap:
                self._labels_cache.pop(next(iter(self._labels_cache)))
        return labels_v

    def _mk_nexthops_cached_factory(
        self,
        fh: np.ndarray,
        slot_cache: list[list[tuple[str, str]]],
        area: str,
    ):
        """Memoized unweighted NextHop construction, shared by the
        unicast general path, the MPLS node-segment loop, and the
        prefix-scoped reassembly fast path.

        Unweighted nexthop sets repeat across prefixes anycast to the
        same originator set and again in the MPLS node-segment loop —
        memoize by the UNION FIRST-HOP COLUMN, not the target ids: in a
        fat-tree every far destination shares the same up-link set, so
        thousands of distinct dest sets collapse into a handful of
        (first-hop set, igp) classes and NextHop construction runs once
        per class instead of once per prefix."""
        mk_memo: dict[tuple, tuple[NextHop, ...]] = {}

        def fh_union_col(targets: np.ndarray) -> np.ndarray:
            if len(targets) == 1:
                return fh[:, int(targets[0])]
            return fh[:, targets].any(axis=1)

        def mk_nexthops_cached(targets: np.ndarray, igp: int):
            col = fh_union_col(targets)
            key = (col.tobytes(), igp)
            got = mk_memo.get(key)
            if got is None:
                got = mk_memo[key] = self._mk_nexthops_union(
                    slot_cache, col, igp, area
                )
            return got

        return mk_nexthops_cached

    def _unicast_general(
        self,
        csr: CsrGraph,
        ls: LinkState,
        my_node: str,
        my_id: int,
        d_root: np.ndarray,
        fh: np.ndarray,
        fh_any: np.ndarray,
        nbr_ids: list[int],
        lfa,
        dist,
        slot_cache: list[list[tuple[str, str]]],
        mk_nexthops_cached,
        items,
        out: dict,
    ) -> list[tuple]:
        """The general per-prefix unicast path (anycast, UCMP, KSP,
        min_nexthop, LFA — and, on the scoped-reassembly path, plain
        prefixes too). Writes routes into `out`; returns the KSP jobs
        for the caller's single batched `_ksp_batch` device call."""
        ksp_jobs: list[tuple] = []  # (prefix, reachable, best_nodes)
        self.spf_kernel_stats["general_prefixes"] += len(items)
        for prefix, per_node in items:
            reachable = {}
            for n, e in per_node.items():
                nid = csr.name_to_id.get(n)
                if n == my_node:
                    reachable[n] = e
                elif (
                    nid is not None
                    and d_root[nid] < INF_DIST
                    and fh_any[nid]
                ):
                    reachable[n] = e
            if not reachable:
                continue
            best_key = max(metric_key(e) for e in reachable.values())
            best_nodes = sorted(
                n for n, e in reachable.items() if metric_key(e) == best_key
            )
            if my_node in best_nodes:
                continue  # local prefix
            if (
                reachable[best_nodes[0]].forwarding_algorithm
                == ForwardingAlgorithm.KSP2_ED_ECMP
            ):
                # batched on device after the loop: ONE vectorized
                # k-disjoint-paths solve for every KSP prefix at once
                # (the reference re-runs Dijkstra per prefix per path †)
                ksp_jobs.append((prefix, reachable, best_nodes))
                continue
            ids = np.array(
                [csr.name_to_id[n] for n in best_nodes], dtype=np.int64
            )
            igps = d_root[ids]
            min_igp = int(igps.min())
            chosen = ids[igps == min_igp]
            chosen_names = sorted(csr.node_names[i] for i in chosen)
            weights = ucmp_weights({n: reachable[n] for n in chosen_names})
            if weights is None:
                nexthops = mk_nexthops_cached(chosen, min_igp)
            else:
                nexthops = self._mk_nexthops(
                    csr, my_id, nbr_ids, fh, chosen, min_igp, ls.area,
                    weights=weights,
                    target_names=csr.node_names,
                    slot_cache=slot_cache,
                )
            if not nexthops:
                continue
            best_entry = reachable[chosen_names[0]]
            if best_entry.min_nexthop and len(nexthops) < best_entry.min_nexthop:
                continue
            backups: tuple[NextHop, ...] = ()
            if lfa is not None:
                backups = self._mk_backup_nexthops(
                    csr, my_id, nbr_ids, fh, lfa, dist, chosen, ls.area,
                    slot_cache,
                )
            out[prefix] = RibEntry(
                prefix=prefix,
                nexthops=nexthops,
                best_node=chosen_names[0],
                best_nodes=tuple(best_nodes),
                best_entry=best_entry,
                igp_cost=min_igp,
                backup_nexthops=backups,
            )
        return ksp_jobs

    def _ksp_batch(
        self,
        csr: CsrGraph,
        ls: LinkState,
        my_node: str,
        my_id: int,
        d_root: np.ndarray,
        jobs: list[tuple],
        out: dict,
    ) -> None:
        """All KSP prefixes in ONE vectorized device call (BASELINE
        config 4): k edge-disjoint paths per job via k successive masked
        batched solves, per-job edge bans as data (ops/ksp.py). Byte-equal
        to the oracle's per-prefix host re-solve (tests/test_ksp_kernel.py
        + the backend-vs-oracle RIB equality suite)."""
        # everything before the first kernel call, under a name of its
        # own: the dense tables' patch, the blocked mask, a destination
        # per job (a numpy call a job), the clamp of k, dist0
        with profiling.annotate("spf:ksp_prepare"):
            # dense tables from the patched device cache (NOT
            # csr.dense_tables(), which would rebuild + re-upload O(V*D)
            # host arrays on every churn rebuild — round-2 verdict item 4);
            # the blocked mask is derived on device (same formula as
            # ops.ksp.build_ksp_blocked)
            dev = self._device_arrays(csr, "dense")
            d_nbr = dev["nbr"]
            d_wgt = dev["wgt"]
            blocked = dev["over"][d_nbr] & (d_nbr != jnp.int32(my_id))
            # destination per job: nearest best node, tie-break by name —
            # name order IS id order (sorted interning), so (dist, id) works
            dests = np.empty(len(jobs), dtype=np.int32)
            for j, (_prefix, _reachable, best_nodes) in enumerate(jobs):
                ids = np.array(
                    [csr.name_to_id[n] for n in best_nodes], dtype=np.int64
                )
                # ids ascending: the first minimum
                dests[j] = ids[np.argmin(d_root[ids])]
            # chunk the job batch by a MEMORY budget, not a constant: the
            # kernel's working set per job is dominated by the [Vp, D] banned
            # mask plus ~3 [Vp, D] i32 intermediates under the k-round scan
            # (round-2 verdict item 4 — a constant 256 put the 100k case at
            # ~1.6 GB per chunk before intermediates)
            vp_d = int(d_nbr.shape[0]) * int(d_nbr.shape[1])
            bytes_per_job = vp_d * 13  # 1B banned + 3 x 4B candidates
            cap = max(8, min(256, (2 << 30) // bytes_per_job))
            chunk = 1 << (cap.bit_length() - 1)  # floor power of two
            max_hops = csr.padded_nodes - 1
            # k CLAMP (round-4 verdict item 5): successive paths ban every
            # parallel slot between each path's node pairs in both
            # directions, so the number of edge-disjoint paths from the
            # root is bounded by its count of DISTINCT NEIGHBORS (each path
            # must leave through a different one), and symmetrically by the
            # dest's. Rounds beyond min(outnbrs(root), max_j innbrs(dest_j))
            # are structurally doomed — don't dispatch their SSSP fixpoints.
            # BASELINE config 4's backbone has degree 2-4 with k=16: this
            # alone cuts the per-prefix solve count ~4x; the in-kernel
            # early exit (ops/ksp.py) handles the per-job dest bound.
            # Neighbor counts are structural, so cache per topology base
            # (LRU like _dev — one entry per area's topology). (src, dst)
            # pairs are unique by construction (_build_csr collapses
            # parallel links via edge_best), so plain bincounts ARE the
            # distinct-neighbor counts. Paths LEAVE the root (out-neighbor
            # bound) and ENTER the dest (in-neighbor bound); the CSR can be
            # asymmetric (a hard-drained adjacency drops one direction), so
            # the two counts differ.
            counts = self._ksp_nbr_counts.get(csr.base_version)
            if counts is None:
                valid = csr.edge_metric < INF_DIST
                counts = (
                    np.bincount(
                        csr.edge_src[valid], minlength=csr.padded_nodes
                    ),
                    np.bincount(
                        csr.edge_dst[valid], minlength=csr.padded_nodes
                    ),
                )
                self._ksp_nbr_counts[csr.base_version] = counts
                while len(self._ksp_nbr_counts) > self._dev_lru_cap:
                    self._ksp_nbr_counts.pop(
                        next(iter(self._ksp_nbr_counts))
                    )
            out_counts, in_counts = counts
            bound = int(
                max(
                    1,
                    min(
                        self.ksp_k,
                        out_counts[my_id],
                        int(in_counts[dests].max()) if len(dests) else 1,
                    ),
                )
            )
            # k is jit-STATIC: bucket the clamp to a power of two so bound
            # shifts under structural churn compile at most
            # log2(ksp_k) + 1 kernel variants per batch shape instead of
            # one per distinct bound (review finding). The in-kernel early
            # exit already stops one probe round past the true bound, so a
            # loose bucket costs at most that single extra round.
            k_eff = min(self.ksp_k, 1 << (bound - 1).bit_length())
            # what names this batch's kernel programs, for
            # prewarm_flap_programs: the batch sizes its chunks pad to
            self._dev[csr.base_version]["host"]["ksp"] = (
                my_id, k_eff, max_hops,
                tuple(sorted({
                    pad_batch(min(chunk, len(jobs) - start))
                    for start in range(0, len(jobs), chunk)
                })),
            )
            # round 1 is ban-free and identical for every job — feed the
            # production solve's own root distances (same overload
            # semantics; oracle-equality tested) so the kernel skips one
            # of the k_eff SSSP fixpoints
            dist0 = np.full(csr.padded_nodes, int(INF_DIST), np.int32)
            m = min(len(d_root), csr.num_nodes)
            dist0[:m] = np.minimum(
                np.asarray(d_root[:m], dtype=np.int64), int(INF_DIST)
            ).astype(np.int32)
            dist0_dev = jnp.asarray(dist0)
        # one span over the whole KSP batch phase (device chunks + host
        # path decode) — the `profile.spf:ksp_ms` stat the device
        # telemetry efficiency join reads (docs/Monitor.md)
        with profiling.annotate("spf:ksp", counters=self.counters):
            self._ksp_chunks(
                jobs, dests, chunk, my_id, d_nbr, d_wgt, blocked, k_eff,
                max_hops, dist0_dev, csr, ls, my_node, out,
            )

    def _ksp_chunks(
        self, jobs, dests, chunk, my_id, d_nbr, d_wgt, blocked, k_eff,
        max_hops, dist0_dev, csr, ls, my_node, out,
    ) -> None:
        from openr_tpu.ops.ksp import (
            ksp_edge_disjoint_dense,
            paths_to_host,
        )
        from openr_tpu.decision.ksp import ksp_route_from_paths

        path_nodes = 0
        for start in range(0, len(jobs), chunk):
            sub = dests[start : start + chunk]
            b = pad_batch(len(sub))
            dsts = np.full(b, my_id, dtype=np.int32)  # padding: dest==root
            dsts[: len(sub)] = sub
            with profiling.annotate("spf:ksp_solve"):
                costs, paths, hops = ksp_edge_disjoint_dense(
                    d_nbr,
                    d_wgt,
                    blocked,
                    jnp.int32(my_id),
                    jnp.asarray(dsts),
                    k=k_eff,
                    max_hops=max_hops,
                    dist0=dist0_dev,
                )
                # the small output's transfer is what waits for the
                # kernel: results ready, with no timing primitive here
                costs = np.asarray(costs)
                compile_ledger.record_transfer(costs.nbytes)
            with profiling.annotate("spf:ksp_fetch"):
                # one fetch of both: the 4 KB of hops start their way
                # with paths and cost no round trip of their own
                paths, hops = jax.device_get((paths, hops))
                compile_ledger.record_transfer(paths.nbytes)
                compile_ledger.record_transfer(hops.nbytes)
            with profiling.annotate("spf:ksp_decode"):
                # the decode follows the hops the kernel found, not the
                # padded width of `paths`: Python ints once a chunk,
                # then a path's live prefix (ops.ksp.paths_to_host)
                path_nodes += int((hops + 1)[costs < INF_DIST].sum())
                cost_rows, hop_rows = costs.tolist(), hops.tolist()
                for j in range(len(sub)):
                    prefix, reachable, best_nodes = jobs[start + j]
                    host_paths = paths_to_host(
                        cost_rows, paths, hop_rows, csr.node_names, j
                    )
                    entry = ksp_route_from_paths(
                        ls, my_node, prefix, reachable, best_nodes,
                        host_paths,
                    )
                    if entry is not None:
                        out[prefix] = entry
            self.spf_kernel_stats["ksp_chunks"] += 1
            self.spf_kernel_stats["ksp_rounds"] += k_eff
        self.spf_kernel_stats["ksp_jobs"] += len(jobs)
        self.spf_kernel_stats["ksp_path_nodes"] += path_nodes

    @staticmethod
    def _mk_backup_nexthops(
        csr: CsrGraph,
        my_id: int,
        nbr_ids: list[int],
        fh: np.ndarray,
        lfa: np.ndarray,
        dist: np.ndarray,
        targets: np.ndarray,
        area: str,
        slot_cache: list[list[tuple[str, str]]],
    ) -> tuple[NextHop, ...]:
        """LFA backups toward `targets`: loop-free neighbors that are not
        already primary first hops for any target. Metric = best
        via-neighbor path cost: metric(root→n) + min over targets of
        dist_n(target)."""
        n_real = len(nbr_ids)
        is_primary = fh[:n_real, targets].any(axis=1)
        is_lfa = lfa[:n_real, targets].any(axis=1)
        out: dict[tuple[str, str], int] = {}
        for n_idx in np.nonzero(is_lfa & ~is_primary)[0]:
            col = 1 + int(n_idx)
            # metric over the targets this neighbor is actually
            # loop-free for (a shorter non-loop-free path must not win)
            via = min(
                int(dist[int(t), col])
                for t in targets
                if lfa[int(n_idx), int(t)]
            )
            link = min(
                d[1] for d in csr.details(my_id, nbr_ids[int(n_idx)])
            )
            m = link + via
            for key in slot_cache[int(n_idx)]:
                if key not in out or m < out[key]:
                    out[key] = m
        return sorted_nexthops(
            NextHop(
                address=fh_name,
                if_name=if_name,
                metric=m,
                neighbor_node=fh_name,
                area=area,
            )
            for (fh_name, if_name), m in out.items()
        )

    @staticmethod
    def _nbr_slot_cache(
        csr: CsrGraph, my_id: int, nbr_ids: list[int]
    ) -> list[list[tuple[str, str]]]:
        """Per-neighbor (fh_name, if_name) slots at the neighbor's
        min-metric parallel links — hoisted out of the per-prefix loop
        (it only depends on my own adjacencies, not the target)."""
        cache: list[list[tuple[str, str]]] = []
        for fh_id in nbr_ids:
            details = csr.details(my_id, fh_id)
            best = min(d[1] for d in details)
            fh_name = csr.node_names[fh_id]
            cache.append(
                [
                    (fh_name, if_name)
                    for if_name, m, _w, _lbl, _oif in details
                    if m == best
                ]
            )
        return cache

    def _mk_nexthops_union(
        self,
        slot_cache: list[list[tuple[str, str]]],
        valid_rows: np.ndarray,  # [N] bool: union first-hop column
        igp: int,
        area: str,
    ) -> tuple[NextHop, ...]:
        """Unweighted nexthop construction from a precomputed union
        first-hop column (the fast path; the weighted/UCMP path keeps
        the per-target accumulation in _mk_nexthops). The result is
        interned into the solver's shared NexthopGroup table, so every
        route class binding the same ECMP set holds the same object."""
        nhs = [
            NextHop(
                address=fh_name,
                if_name=if_name,
                metric=igp,
                neighbor_node=fh_name,
                area=area,
            )
            for n_idx in np.nonzero(valid_rows)[0]
            for (fh_name, if_name) in slot_cache[int(n_idx)]
        ]
        return self._nh_intern.intern(sorted_nexthops(nhs))

    def _mk_nexthops(
        self,
        csr: CsrGraph,
        my_id: int,
        nbr_ids: list[int],
        fh: np.ndarray,
        targets: np.ndarray,
        igp: int,
        area: str,
        weights: dict[str, int] | None = None,
        target_names=None,
        slot_cache: list[list[tuple[str, str]]] | None = None,
    ) -> tuple[NextHop, ...]:
        """Union of valid first-hop interfaces toward `targets` (all at the
        same IGP distance). Parallel links at min metric each get a nexthop.
        With `weights` (UCMP), nexthop weight = gcd-normalized sum of the
        weights of the targets it serves — identical rule to the oracle's
        _nexthops_to_nodes."""
        if slot_cache is None:
            slot_cache = TpuSpfSolver._nbr_slot_cache(csr, my_id, nbr_ids)
        slots: dict[tuple[str, str], None] = {}
        wsum: dict[tuple[str, str], int] = {}
        visits = 0
        for tgt in targets:
            valid = np.nonzero(fh[:, int(tgt)])[0]
            visits += len(valid)
            for n_idx in valid:
                for key in slot_cache[int(n_idx)]:
                    slots[key] = None
                    if weights is not None:
                        wsum[key] = (
                            wsum.get(key, 0)
                            + weights[target_names[int(tgt)]]
                        )
        if weights is not None:
            wsum = normalize_weights(wsum)
            self.spf_kernel_stats["ucmp_prefixes"] += 1
            self.spf_kernel_stats["ucmp_slot_visits"] += visits
        nhs = [
            NextHop(
                address=fh_name,
                if_name=if_name,
                metric=igp,
                weight=wsum.get((fh_name, if_name), 0)
                if weights is not None
                else 0,
                neighbor_node=fh_name,
                area=area,
            )
            for (fh_name, if_name) in slots
        ]
        return sorted_nexthops(nhs)
