"""Central registry of counter / gauge / stat / marker names.

Every name a module stamps into its :class:`Counters` registry — and
every perf-trace stage marker — is declared here, so the observable
surface of the system is one reviewable module instead of string
literals scattered across the tree. ``tools/orlint`` rule **OR007**
enforces it both ways:

  * every literal (or f-string template) passed to
    ``Counters.increment/set/add_value/touch`` or
    ``PerfEvents.start/add_perf_event`` anywhere in ``openr_tpu`` must
    resolve against this registry;
  * every name in :data:`DOCUMENTED` (and every template's
    :data:`TEMPLATES` doc-form, and every marker) must appear in
    ``docs/Monitor.md`` — this subsumes the three bash-heredoc doc
    lints ci.sh used to carry.

Adding a counter: add the literal to :data:`COUNTERS` (or a template to
:data:`TEMPLATES` when the name embeds a runtime key), and — for the
operator-facing families — a row to docs/Monitor.md. docs/Linting.md
covers the policy.
"""

from __future__ import annotations

from openr_tpu.monitor import perf

# --------------------------------------------------------------- markers

#: perf-trace stage marker vocabulary (each must appear in
#: docs/Monitor.md; stamp sites may only use these).
MARKERS: tuple[str, ...] = perf.ALL_MARKERS

#: non-marker public attributes of monitor.perf that `perf.<NAME>`
#: references may legitimately touch (the OR007 attr check's allowlist).
PERF_MODULE_EXPORTS: frozenset[str] = frozenset(
    {"ALL_MARKERS", "MAX_EVENTS_PER_TRACE"}
)

# -------------------------------------------------------------- counters

#: exact counter / gauge / stat names (literal emit sites).
COUNTERS: frozenset[str] = frozenset(
    {
        # decision
        "decision.lsdb_changes",
        "decision.rebuild.full",
        "decision.rebuild.prefix_only",
        "decision.rebuild.topo_delta",
        "decision.rebuild.cached_areas",
        "decision.rebuild.area_solves",
        "decision.rebuild.failed",
        # rebuilds whose dirt held a structural topology change
        "decision.rebuild.structural",
        # how LinkState.to_csr served: a build (_build_csr) / a base
        # derived from an overload-only change without one
        "decision.csr.builds",
        "decision.csr.overload_rebases",
        # adjacencies the builds' walks read (every one of the area's)
        "decision.csr.adjacencies_walked",
        # databases whose build columns a build made anew
        "decision.csr.databases_converted",
        # builds that kept the last build's node interning (same node set)
        "decision.csr.interning_kept",
        # rebuilds published with an empty update: Fib is told nothing
        "decision.rebuild.no_change",
        # a rebuild's PrefixState snapshot: handed out again (no prefix
        # changed since the last one) / built (one outer-dict copy)
        "decision.snapshot.prefix_shared",
        "decision.snapshot.prefix_copied",
        # merge-book fallback matrix (docs/Decision.md): scoped = the
        # delta fold patched the persistent merged RIB in place; full =
        # a first-build/policy/mismatch round re-armed it from scratch
        "decision.merge.scoped",
        "decision.merge.full",
        "decision.rebuild_ms",
        "decision.spf.solves",
        "decision.spf.warm_starts",
        "decision.spf.warm_fallbacks",
        "decision.spf.warm_region_nodes",
        # LFA backups (TpuSpfSolver._lfa_backups, set from
        # spf_kernel_stats): routes given one or more, next hops built,
        # routes given none
        "decision.spf.lfa_routes",
        "decision.spf.lfa_nexthops",
        "decision.spf.lfa_unprotected",
        "decision.spf_ms",
        "decision.spf_runs",
        "decision.spf_solve_ms",
        # decision: nexthop-group intern table size (gauge)
        "decision.nexthop_groups",
        # fib
        "fib.perf_traces_completed",
        "fib.program_ok",
        "fib.program_fail",
        "fib.program_fail_streak",
        "fib.program_ms",
        # delta-native programming (docs/Fib.md): batched chunk calls,
        # per-chunk size stat, routes written, delta-book scan size
        "fib.program_batches",
        "fib.program_batch_size",
        "fib.program_scan_routes",
        "fib.routes_programmed",
        "fib.warm_boot_reprogrammed",
        "fib.warm_boot_routes",
        # kvstore
        "kvstore.expired_keys",
        "kvstore.flood_backpressure_drops",
        "kvstore.flood_bytes",
        "kvstore.flood_decode_ms",
        "kvstore.flood_encode_ms",
        "kvstore.flood_encodes",
        "kvstore.flood_failures",
        "kvstore.flood_fanout_ms",
        # cross-node flood tracing (docs/Monitor.md "Flood tracing"):
        # sampled originations, relayed hop-span stamps, span wire bytes
        "kvstore.flood_traces_sampled",
        "kvstore.flood_hops",
        "kvstore.flood_span_bytes",
        "kvstore.flood_keys_coalesced",
        "kvstore.flood_root_missing",
        "kvstore.floods_held",
        "kvstore.floods_rate_limited",
        "kvstore.floods_received",
        "kvstore.floods_sent",
        "kvstore.full_sync_failures",
        "kvstore.full_sync_keys_sent",
        "kvstore.full_sync_probe_miss",
        "kvstore.full_syncs",
        "kvstore.full_syncs_legacy",
        "kvstore.full_syncs_noop",
        "kvstore.full_syncs_noop_served",
        "kvstore.full_syncs_served",
        "kvstore.merged_updates",
        "kvstore.peer_disconnects",
        "kvstore.peer_reconnects",
        "kvstore.peers_added",
        "kvstore.peers_rejected_bad_area",
        "kvstore.peers_removed",
        "kvclient.advertisements",
        # rpc wire accounting (rpc/core.py; every RpcServer/RpcClient
        # with a Counters registry stamps these)
        "rpc.bytes_rx",
        "rpc.bytes_tx",
        "rpc.conns_binary",
        # spark / linkmonitor
        "spark.bad_packets",
        "spark.handshake_recv",
        "spark.handshake_sent",
        "spark.heartbeat_sent",
        "spark.hello_recv",
        "spark.hello_sent",
        "spark.chaos_dropped",
        "spark.inbox_dropped",
        "spark.neighbor_down",
        "spark.neighbor_up",
        "spark.nongr_restarts_detected",
        "spark.restart_announced",
        "linkmonitor.adj_advertised",
        "linkmonitor.flap_damped",
        "linkmonitor.neighbor_down",
        "linkmonitor.neighbor_up",
        # ctrl / watchdog / monitor
        "ctrl.sub_evictions",
        "watchdog.aborts",
        "watchdog.scans",
        "watchdog.stalls",
        "monitor.convergence_ms",
        "monitor.flood_traces",
        "monitor.log_samples",
        "monitor.perf_traces",
        "monitor.perf_traces_multi_origin",
        # persist plane (persist/plane.py; docs/Persist.md): journal
        # append/compaction accounting + recovery footprint from boot
        "persist.appends",
        "persist.append_errors",
        "persist.journal_bytes",
        "persist.journal_records",
        "persist.fsyncs",
        "persist.compactions",
        "persist.compact_errors",
        "persist.recovered_records",
        "persist.truncated_bytes",
        # wire/persist schema lock (types/wirelock.py; docs/Wire.md
        # "Schema evolution"): the lock_version this node was built
        # against, stamped as a gauge at Node construction — fleet
        # monitoring catches version skew before it mis-decodes
        "wire.schema_lock_version",
        # everything else
        "configstore.corrupt",
        "configstore.stores",
        "nlifaces.events",
        "platform.errors",
        "prefix_allocator.allocations",
        "prefixmgr.advertised",
        "prefixmgr.events",
        "prefixmgr.policy_denied",
        "prefixmgr.range_chunks",
        "prefixmgr.range_prefixes",
        "prefixmgr.redistributed",
        # entry-book footprint gauge at the advertisement-sync edge —
        # a leak detector for the delta redistribution books
        "prefixmgr.redistribute.book_size",
        # common/tasks guard_task default
        "task.uncaught_exceptions",
        # jax compile ledger (monitor/compile_ledger.py; process-wide)
        "jax.compiles.total",
        "jax.transfers.host_reads",
        "jax.transfers.host_bytes",
        # the cyclic garbage collector's process totals (monitor/
        # profiling.py export_gc_to; docs/Monitor.md "Runtime: the
        # garbage collector"): cumulative gauges set at a rebuild's edge
        "runtime.gc.collections",
        "runtime.gc.pause_ms",
        "runtime.gc.full_collections",
        "runtime.gc.full_pause_ms",
    }
)

#: f-string templates (``*`` = runtime-interpolated segment), mapped to
#: the doc-form docs/Monitor.md uses when the family is documented
#: (None = internal family, registry membership only).
TEMPLATES: dict[str, str | None] = {
    # messaging queue gauge/counter fields — one row per field in
    # docs/Monitor.md (the queue name is free)
    "queue.*.depth": "queue.<name>.depth",
    "queue.*.highwater": "queue.<name>.highwater",
    "queue.*.blocked": "queue.<name>.blocked",
    "queue.*.coalesced": "queue.<name>.coalesced",
    "queue.*.shed": "queue.<name>.shed",
    "queue.*.overflow": "queue.<name>.overflow",
    # module-keyed lifecycle counters (OpenrModule)
    "*.fiber_crashes": None,
    "*.timer_errors": None,
    "*.task_exceptions": None,
    "*.subscribers": None,
    # decision engine substructure
    "decision.decode.*": None,
    "decision.dev_cache.*": None,
    "decision.elect.*": None,
    "decision.spf.*": None,
    # per-jitted-function compile counts (monitor/compile_ledger.py) —
    # the fn segment is the jit wrapper's name
    "jax.compiles.*": "jax.compiles.<fn>",
    # steady-state work ledger (monitor/work_ledger.py): per-pipeline-
    # stage entities-touched / delta-size / proportionality-ratio
    # gauges; the stage segment is a work_ledger.STAGES name. `.ratio`
    # is a ratio-type gauge — fleet aggregation must never sum it
    # (monitor/fleet.py).
    "work.*.touched": "work.<stage>.touched",
    "work.*.delta": "work.<stage>.delta",
    "work.*.ratio": "work.<stage>.ratio",
    # kernel cost ledger (monitor/device.py): XLA cost/memory analysis
    # of each canonical jitted entry point, exported per (fn, field)
    "jax.kernel.*.*": "jax.kernel.<fn>.<field>",
    # per-device HBM gauges (monitor/device.py sample_hbm; absent on
    # backends whose memory_stats() returns None — the CPU degradation)
    "device.*.hbm_bytes_in_use": "device.<i>.hbm_bytes_in_use",
    "device.*.hbm_peak_bytes": "device.<i>.hbm_peak_bytes",
    "device.*.hbm_limit_bytes": "device.<i>.hbm_limit_bytes",
    # annotated profiling spans' wall durations (monitor/profiling.py
    # annotate(counters=...)) — the span segment is the annotation name
    "profile.*_ms": "profile.<span>_ms",
    # platform error taxonomy
    "platform.*": None,
}

# ----------------------------------------------------------------- spans

#: the spans of one route rebuild, publication to push, in the order a
#: rebuild meets them (monitor/profiling.py; docs/Monitor.md "Spans").
#: Decision.last_breakdown_ms publishes every one of them each rebuild,
#: 0.0 where the branch did not run. Indented = opened inside the one
#: above. The prefix is what perfbench/trace_reduce.py keeps from the
#: host planes of a profiler trace: ^(spf|decision|fib|kvstore):
REBUILD_SPANS: tuple[str, ...] = (
    "decision:debounce_wait",    # first publication buffered → rebuild
    "decision:rebuild",          # the rebuild coroutine, all of it
    "decision:decode",           #   serde decode of the batch (thread)
    "decision:apply_snapshot",   #   LSDB apply, dirt, snapshot (loop)
    "decision:snapshot",         #     the LSDB view the solver works on
    "decision:compute_diff",     #   the solver thread, as the loop waits
    "decision:thread_start",     #     to_thread → the thread runs (record)
    "decision:compute_rib",      #     per-area compute + merge
    "spf:to_csr",                #       LinkState → CSR snapshot
    "spf:csr_index",             #         build: the bidirectional index
    "spf:csr_walk",              #         build: the walk over adjacencies
    "spf:csr_collapse",          #         build: parallel edges to min
    "spf:csr_sort",              #         build: dst sort, arrays, index
    "spf:csr_masks",             #         build: node masks
    "spf:prepare",               #       cold: to_csr + pads + dispatch
    "spf:dispatch",              #       device tables: hit, patch, build
    "spf:patch_scatter",         #         journal suffix → compiled scatters
    "spf:table_build",           #         new base: the host's table build
    "spf:upload",                #         new base: tables → device, waited for
    "spf:batched_solve",         #       cold fused kernel + packed fetch
    "spf:sharded_solve",         #       mesh kernel, dispatch only
    "spf:native_solve",          #       C++ host engine
    "spf:unpack",                #       packed buffer → d_root, fh, lfa
    "spf:rib_assembly",          #       election + assembly + mpls
    "spf:rib_election",          #         classes, advertiser election
    "spf:rib_unicast",           #         unicast RibEntries
    "spf:unicast_general",       #           the scalar election (warm too)
    "spf:lfa_backups",           #           LFA backups, one pass a rebuild
    "spf:lfa_dist_fetch",        #             [vp, B] matrix → host for them
    "spf:rib_mpls",              #         node-label routes
    "spf:ksp_prepare",           #         KSP: dests, masks, k clamp, dist0
    "spf:ksp",                   #         KSP prefixes' batched paths
    "spf:ksp_solve",             #           a chunk: dispatch → costs on the host
    "spf:ksp_fetch",             #           a chunk: paths → host
    "spf:ksp_decode",            #           a chunk: paths → RibEntries
    "spf:dist_mirror",           #       warm: [vp, B] matrix → host
    "spf:warm_cone",             #       warm: host cone walk
    "spf:warm_scatter",          #       warm: cone → INF, compiled scatter
    "spf:warm_solve",            #       warm kernel + packed fetch
    "spf:warm_unpack",           #       warm: unpack + change mask
    "spf:warm_reassemble",       #       warm: scoped routes + MPLS
    "spf:warm_scope",            #         change mask, view, `touched` set
    "spf:general_items",         #         scoped items sorted and copied
    "spf:warm_table_copy",       #         cached RIB copied, `touched` put in
    "spf:warm_labels",           #         label routes of the changed nodes
    "spf:gc",                    #       a collection (gen ≥ 1) under spf:
    "decision:merge_full",       #       cross-area fold, all of it
    "decision:merge_scope",      #       cross-area fold, the scoped keys
    "decision:diff",             #     RIB delta / work-ledger commit
    "decision:thread_return",    #     thread done → the loop resumes (record)
    "decision:gc",               #   a collection (gen ≥ 1) under decision:
    "decision:export_counters",  #   markers, trim policy, counters
    "decision:publish",          #   merge book + route_updates.push
    "spf:prewarm",               # after a full rebuild: the flap's programs
)

#: every span name the program opens (tests/test_profiling.py checks the
#: call sites against it; docs/Monitor.md lists them)
SPANS: frozenset[str] = frozenset(REBUILD_SPANS) | {"fib:program", "fib:gc"}

#: the queue counter FIELD vocabulary the messaging seams may emit —
#: OR007 statically cross-checks messaging/__init__.py's emit sites
#: against this set (the old ci.sh heredoc #4, now AST-based).
QUEUE_FIELDS: frozenset[str] = frozenset(
    {"depth", "highwater", "blocked", "coalesced", "shed", "overflow"}
)

#: names whose presence in docs/Monitor.md is REQUIRED (the
#: operator-facing families the retired ci.sh heredocs covered; the
#: rest of COUNTERS follows Monitor.md's generic `<module>.<what>`
#: convention and only needs registry membership).
DOCUMENTED: frozenset[str] = frozenset(
    {n for n in COUNTERS if n.startswith("decision.rebuild.")}
    | {n for n in COUNTERS if n.startswith("decision.merge.")}
    | {n for n in COUNTERS if n.startswith("decision.spf.warm_")}
    | {n for n in COUNTERS if n.startswith("prefixmgr.redistribute.")}
    | {n for n in COUNTERS if n.startswith("kvstore.flood")}
    | {n for n in COUNTERS if n.startswith("kvstore.full_sync")}
    | {n for n in COUNTERS if n.startswith("rpc.")}
    | {n for n in COUNTERS if n.startswith("fib.program")}
    | {n for n in COUNTERS if n.startswith("ctrl.sub_")}
    | {n for n in COUNTERS if n.startswith("watchdog.")}
    | {n for n in COUNTERS if n.startswith("spark.inbox_")}
    | {n for n in COUNTERS if n.startswith("jax.")}
    | {n for n in COUNTERS if n.startswith("persist.")}
    | {n for n in COUNTERS if n.startswith("wire.")}
    | {n for n in COUNTERS if n.startswith("runtime.")}
)

#: source files exempt from the per-callsite check: the registry's own
#: mechanics (Counters expands `<stat>.sum` etc. dynamically) and the
#: messaging seams (covered by the dedicated QUEUE_FIELDS cross-check).
CALLSITE_EXEMPT: tuple[str, ...] = (
    "openr_tpu/monitor/counters.py",
    "openr_tpu/monitor/names.py",
    "openr_tpu/messaging/__init__.py",
)


def is_registered(name_or_template: str) -> bool:
    """True when a literal name or normalized f-string template resolves
    against the registry (exact counter, exact template, or a literal
    matching one template)."""
    import fnmatch

    if name_or_template in COUNTERS or name_or_template in TEMPLATES:
        return True
    if "*" in name_or_template:
        return False
    return any(
        fnmatch.fnmatchcase(name_or_template, t) for t in TEMPLATES
    )
