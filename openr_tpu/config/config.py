"""Config schema + validation (reference: openr/if/OpenrConfig.thrift †,
openr/config/Config.cpp † populateInternalDb-style checks)."""

from __future__ import annotations

import json
from dataclasses import dataclass, field, replace

from openr_tpu.common import constants as C
from openr_tpu.types.network import IpPrefix
from openr_tpu.types.serde import from_wire
from openr_tpu.types.topology import (
    ForwardingAlgorithm,
    ForwardingType,
    PrefixMetrics,
)


class ConfigError(ValueError):
    """Invalid configuration (reference: Config.cpp throws std::invalid_argument †)."""


@dataclass
class SparkConfig:
    """reference: OpenrConfig.thrift † SparkConfig."""

    hello_time_ms: int = C.SPARK_HELLO_INTERVAL_MS
    fastinit_hello_time_ms: int = C.SPARK_FASTINIT_HELLO_INTERVAL_MS
    handshake_time_ms: int = C.SPARK_HANDSHAKE_INTERVAL_MS
    keepalive_time_ms: int = C.SPARK_HEARTBEAT_INTERVAL_MS
    hold_time_ms: int = C.SPARK_HOLD_TIME_MS
    graceful_restart_time_ms: int = C.SPARK_GR_HOLD_TIME_MS
    # tx packet framing (docs/Wire.md): "bin" = compact binary, "json"
    # = legacy canonical JSON. RX always sniffs, so mixed-codec
    # neighbors interoperate. (Appended field: binary wire schema
    # evolution is append-only.)
    wire_codec: str = "bin"


@dataclass
class KvstoreConfig:
    """reference: OpenrConfig.thrift † KvstoreConfig."""

    key_ttl_ms: int = C.KVSTORE_DEFAULT_TTL_MS
    sync_interval_s: int = C.KVSTORE_SYNC_INTERVAL_S
    flood_rate_msgs_per_sec: int = C.KVSTORE_FLOOD_RATE_MSGS_PER_SEC
    flood_rate_burst_size: int = C.KVSTORE_FLOOD_RATE_BURST
    # bound on a peer's coalesced pending-flood queue; overflow drops the
    # backlog and schedules a FULL_SYNC (backpressure)
    flood_pending_max_keys: int = C.KVSTORE_FLOOD_PENDING_MAX_KEYS
    enable_flood_optimization: bool = False
    # DUAL flood-root eligibility (reference: is_flood_root †). The
    # reference restricts root eligibility to a few well-connected
    # nodes; every-node-a-root means O(V) root machines per node, so
    # the default is False and deployments elect roots explicitly:
    # either set is_flood_root on ~2 nodes, or list candidate node
    # names in flood_root_candidates (same config on every node; a node
    # is root iff its own name is listed — overrides is_flood_root).
    is_flood_root: bool = False
    flood_root_candidates: tuple[str, ...] = ()
    # grace before declaring KVSTORE_SYNCED with zero peers (covers the
    # window before LinkMonitor delivers the first PeerEvent)
    initial_sync_grace_s: float = 2.0
    # cross-node flood tracing (docs/Monitor.md "Flood tracing"):
    # deterministic head-sampling — every Nth ACCEPTED local origination
    # carries a per-hop flood span cluster-wide. 0 disables tracing
    # (the default: span stamps cost wire bytes on every sampled hop).
    # The sampling phase is derived from (node_name, trace_seed) so a
    # seeded emulation replays the same sampled set while different
    # nodes stay decorrelated. Affordability guidance: a coalesced
    # flood batch is traced when ANY merged origination was sampled
    # (per-frame taint ≈ 1-(1-1/N)^batch), so size N with the CLUSTER
    # — a few × node count under heavy churn keeps the wire overhead
    # in low single digits (measured: docs/Monitor.md, BENCH_TRACE);
    # each sampled origination still completes a span on every node
    # it reaches, so trace volume stays ample.
    trace_sample_every: int = 0
    trace_seed: int = 0


@dataclass
class MessagingConfig:
    """Bounds + overflow policies for the inter-module queues
    (openr_tpu/messaging). The reference's ReplicateQueues are unbounded;
    under sustained churn that is an OOM waiting to happen, so every
    policied seam here gets a depth cap (DeltaPath, PAPERS.md: churn
    throughput is governed by batching/coalescing at the seams)."""

    # per-reader depth cap for the policied queues (kvstore_pubs,
    # route_updates, fib_updates coalesce; log_samples, perf_events
    # shed-oldest). 0 = unbounded.
    queue_maxsize: int = C.QUEUE_MAXSIZE
    # False keeps the caps configured (the soak's bounded-depth invariant
    # still reads queue_maxsize) but builds the queues UNBOUNDED — the
    # deliberately-broken control case that proves the watermark check
    # catches unbounded growth.
    enforce_bounds: bool = True


@dataclass
class LinkMonitorConfig:
    """reference: OpenrConfig.thrift † LinkMonitorConfig."""

    linkflap_initial_backoff_ms: int = C.LINK_FLAP_INITIAL_BACKOFF_MS
    linkflap_max_backoff_ms: int = C.LINK_FLAP_MAX_BACKOFF_MS
    use_rtt_metric: bool = False
    include_interface_regexes: tuple[str, ...] = ()
    exclude_interface_regexes: tuple[str, ...] = ()


@dataclass
class DecisionConfig:
    """reference: OpenrConfig.thrift † DecisionConfig."""

    debounce_min_ms: int = C.DECISION_DEBOUNCE_MIN_MS
    debounce_max_ms: int = C.DECISION_DEBOUNCE_MAX_MS
    # TPU solver knobs (rebuild-specific)
    use_tpu_solver: bool = True  # False → CPU oracle path (tests/tiny nodes)
    # native C++ radix-heap solver (native/spf) for the single-root RIB
    # path: "auto" (use when built and LFA off), "on", "off"
    native_rib: str = "auto"
    enable_lfa: bool = False
    # edge-disjoint paths per SR-MPLS KSP prefix (reference hardwires 2
    # in KSP2_ED_ECMP †; BASELINE config 4 exercises k=16; the batched
    # kernel supports k<=16 — validated)
    ksp_paths: int = 2
    # multi-chip mesh for BATCHED solves (fleet/all-sources shapes):
    # sources × graph device grid (parallel.make_mesh). 0 = off
    # (single device). Requires mesh_sources × mesh_graph ≤ available
    # jax devices; the single-root production rebuild always stays
    # single-device (latency shape).
    mesh_sources: int = 0
    mesh_graph: int = 1
    # topology-delta warm start (DeltaPath/Bounded-Dijkstra): metric-only
    # link churn re-solves only the affected region from the cached
    # SolveArtifact instead of paying a full per-area solve
    # (REBUILD_TOPO_DELTA; docs/Decision.md). False forces every
    # topology change down the full path.
    enable_topo_delta: bool = True
    # fallback-to-full threshold: a warm start is refused when the
    # changed-edge DELTA SET exceeds this fraction of the graph's
    # edges — past that a cold solve is cheaper than per-edge
    # bookkeeping. The affected REGION is deliberately uncapped: it may
    # legitimately cover most of the graph (a raised edge near the
    # root of a uniform-metric topology), and its worst case costs
    # about one cold solve.
    topo_delta_max_frac: float = 0.25


@dataclass
class FibConfig:
    """reference: OpenrConfig.thrift † (fib port etc.)."""

    initial_retry_ms: int = C.FIB_INITIAL_RETRY_MS
    max_retry_ms: int = C.FIB_MAX_RETRY_MS
    sync_interval_s: int = C.FIB_SYNC_INTERVAL_S
    dry_run: bool = False
    # warm boot (graceful restart dataplane continuity): read the
    # previous incarnation's programmed routes at start and program only
    # the delta against the first computed RIB — never flush (reference:
    # Fib warm-boot sync †, SURVEY §5.3/5.4)
    enable_warm_boot: bool = True
    # max routes per FibService add/delete call on the delta program
    # path (docs/Fib.md): a million-route convergence ships bounded
    # chunks instead of one giant frame. Appended field (wire evolution:
    # older peers default it).
    program_batch_size: int = 4096


@dataclass
class SegmentRoutingConfig:
    """reference: OpenrConfig.thrift † SegmentRoutingConfig (sr_enable,
    label ranges)."""

    enable: bool = False
    node_segment_label: int = 0  # 0 = auto-allocate from range
    sr_global_range: tuple[int, int] = C.SR_GLOBAL_RANGE
    sr_local_range: tuple[int, int] = C.SR_LOCAL_RANGE


@dataclass
class WatchdogConfig:
    """reference: OpenrConfig.thrift † WatchdogConfig."""

    enable: bool = True
    interval_s: int = C.WATCHDOG_INTERVAL_S
    thread_timeout_s: int = C.WATCHDOG_THREAD_TIMEOUT_S


@dataclass
class AreaConfig:
    """reference: OpenrConfig.thrift † AreaConfig (area id + interface /
    neighbor membership regexes)."""

    area_id: str = C.DEFAULT_AREA
    include_interface_regexes: tuple[str, ...] = (".*",)
    neighbor_regexes: tuple[str, ...] = (".*",)


@dataclass
class OriginatedPrefix:
    """reference: OpenrConfig.thrift † OriginatedPrefix."""

    prefix: str = ""
    forwarding_type: ForwardingType = ForwardingType.IP
    forwarding_algorithm: ForwardingAlgorithm = ForwardingAlgorithm.SP_ECMP
    path_preference: int = 1000
    source_preference: int = 100
    minimum_supporting_routes: int = 0
    install_to_fib: bool = False
    tags: tuple[str, ...] = ()


@dataclass(frozen=True)
class PolicyStatementConfig:
    """Config mirror of policy.PolicyStatement (kept here so the config
    schema has no dependency on the policy engine; OpenrNode converts).
    reference: PolicyStatement in openr/policy/ †."""

    name: str = ""
    match_tags: tuple[str, ...] = ()
    match_prefixes: tuple[str, ...] = ()
    action_accept: bool = True
    set_path_preference: int | None = None
    set_source_preference: int | None = None
    set_distance_increment: int | None = None
    add_tags: tuple[str, ...] = ()


@dataclass
class RouteMapTermConfig:
    """Config mirror of policy.RouteMapTerm (ordered route-map term).
    `match_prefixes` entries are "PREFIX [ge N] [le N]" strings, parsed
    by OpenrNode at assembly. reference: openr/policy/ † ordered
    statement evaluation."""

    seq: int = 0
    action: str = "permit"
    match_tags_any: tuple[str, ...] = ()
    match_tags_all: tuple[str, ...] = ()
    match_not_tags: tuple[str, ...] = ()
    match_prefixes: tuple[str, ...] = ()
    set_path_preference: int | None = None
    set_source_preference: int | None = None
    set_distance_increment: int | None = None
    set_tags: tuple[str, ...] | None = None
    add_tags: tuple[str, ...] = ()
    remove_tags: tuple[str, ...] = ()


@dataclass
class PrefixAllocationConfig:
    """reference: OpenrConfig.thrift † PrefixAllocationConfig — carve
    `seed_prefix` into /alloc_prefix_len blocks; each node elects a
    collision-free block index through KvStore write conflicts."""

    seed_prefix: str = ""
    alloc_prefix_len: int = 0
    # STATIC mode pins the index instead of electing (reference:
    # prefix_allocation_mode †)
    static_index: int | None = None


@dataclass
class UdpInterfaceConfig:
    """One point-to-point UDP 'interface' for a standalone deployment
    without per-interface kernel multicast: Spark's hello traffic for
    `if_name` is carried on a local UDP port bound to a fixed peer
    (reference: the IoProvider abstraction † makes the packet path
    pluggable; this is the cross-host provider's link definition)."""

    if_name: str
    local_port: int
    peer_host: str
    peer_port: int


@dataclass
class TlsConfig:
    """Control-plane TLS (reference: thrift server TLS knobs †, the
    ctrl-server's optional secure thrift). Applied to the ctrl listener
    and the KvStore RPC mesh; contexts built by openr_tpu.rpc.tls."""

    enabled: bool = False
    cert_path: str = ""
    key_path: str = ""
    ca_path: str = ""  # trust anchor for peer verification (both sides)
    # require a verified client certificate (router-to-router mutual
    # auth); operator CLIs without certs need this off on ctrl
    require_client_cert: bool = True


@dataclass
class NodeConfig:
    """Root config document (reference: OpenrConfig.thrift † OpenrConfig)."""

    node_name: str = ""
    areas: tuple[AreaConfig, ...] = (AreaConfig(),)
    spark: SparkConfig = field(default_factory=SparkConfig)
    kvstore: KvstoreConfig = field(default_factory=KvstoreConfig)
    messaging: MessagingConfig = field(default_factory=MessagingConfig)
    link_monitor: LinkMonitorConfig = field(default_factory=LinkMonitorConfig)
    decision: DecisionConfig = field(default_factory=DecisionConfig)
    fib: FibConfig = field(default_factory=FibConfig)
    segment_routing: SegmentRoutingConfig = field(
        default_factory=SegmentRoutingConfig
    )
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)
    originated_prefixes: tuple[OriginatedPrefix, ...] = ()
    # origination policy statements applied by PrefixManager before a
    # prefix is advertised (reference: area_policies / PolicyManager †);
    # empty = accept everything
    prefix_policy_statements: tuple["PolicyStatementConfig", ...] = ()
    prefix_policy_default_accept: bool = True
    # ordered route-map (numbered terms, first-match-wins, implicit
    # deny unless prefix_route_map_default_accept) — takes precedence
    # over prefix_policy_statements when non-empty
    prefix_route_map: tuple["RouteMapTermConfig", ...] = ()
    prefix_route_map_default_accept: bool = False
    prefix_allocation: PrefixAllocationConfig | None = None
    enable_v4: bool = True
    enable_best_route_selection: bool = True
    # ports (0 = ephemeral, for in-process multi-node tests)
    ctrl_port: int = C.CTRL_PORT
    kvstore_port: int = C.KVSTORE_PORT
    dry_run: bool = False
    # standalone-process deployment: static point-to-point UDP links for
    # Spark when kernel multicast interfaces aren't used (python -m
    # openr_tpu); empty = interfaces come from netlink
    udp_interfaces: tuple[UdpInterfaceConfig, ...] = ()
    # host to bind kvstore/ctrl listeners + advertise to neighbors
    endpoint_host: str = "127.0.0.1"
    # optional control-plane TLS (ctrl + kvstore RPC listeners/dialers)
    tls: TlsConfig = field(default_factory=TlsConfig)


class Config:
    """Validated accessor wrapper (reference: openr/config/Config †)."""

    def __init__(self, node: NodeConfig):
        self.node = node
        self._validate()

    # ---- construction -----------------------------------------------------

    @staticmethod
    def from_json(text: str | bytes) -> "Config":
        return Config(from_wire(text, NodeConfig))

    @staticmethod
    def from_file(path: str) -> "Config":
        with open(path, "rb") as f:
            return Config.from_json(f.read())

    @staticmethod
    def default(node_name: str, **overrides) -> "Config":
        return Config(replace(NodeConfig(node_name=node_name), **overrides))

    def to_json(self) -> str:
        from openr_tpu.types.serde import to_jsonable

        # straight through the jsonable tree — no encode-to-canonical-
        # bytes-then-reparse round trip
        return json.dumps(to_jsonable(self.node), indent=2)

    # ---- validation (reference: Config::populateInternalDb checks †) ------

    def _validate(self) -> None:
        n = self.node
        try:
            C.validate_name(n.node_name, "node_name")
        except ValueError as e:
            raise ConfigError(str(e)) from e
        if not n.areas:
            raise ConfigError("at least one area required")
        seen = set()
        for a in n.areas:
            try:
                C.validate_name(a.area_id, "area_id")
            except ValueError as e:
                raise ConfigError(str(e)) from e
            if a.area_id in seen:
                raise ConfigError(f"duplicate area {a.area_id!r}")
            seen.add(a.area_id)
        s = n.spark
        if not (
            0 < s.fastinit_hello_time_ms <= s.hello_time_ms
        ):
            raise ConfigError("spark: fastinit must be <= hello interval")
        if s.hold_time_ms < 3 * s.keepalive_time_ms:
            raise ConfigError(
                "spark: hold_time must be >= 3x keepalive "
                "(reference: Config.cpp † hold/keepalive check)"
            )
        if s.wire_codec not in ("bin", "json"):
            raise ConfigError("spark: wire_codec must be bin|json")
        d = n.decision
        if not (0 < d.debounce_min_ms <= d.debounce_max_ms):
            raise ConfigError("decision: debounce min must be <= max")
        if not (1 <= d.ksp_paths <= 16):
            raise ConfigError(
                "decision: ksp_paths must be in 1..16 (the vectorized "
                "k-disjoint-paths kernel bound — ops/ksp.py)"
            )
        if d.native_rib not in ("auto", "on", "off"):
            raise ConfigError(
                "decision: native_rib must be auto|on|off"
            )
        if d.mesh_sources < 0 or d.mesh_graph < 1:
            raise ConfigError(
                "decision: mesh_sources must be >= 0 and mesh_graph >= 1"
            )
        k = n.kvstore
        if k.key_ttl_ms <= 0:
            raise ConfigError("kvstore: key_ttl_ms must be positive")
        if n.messaging.queue_maxsize < 0:
            raise ConfigError("messaging: queue_maxsize must be >= 0")
        f = n.fib
        if not (0 < f.initial_retry_ms <= f.max_retry_ms):
            raise ConfigError("fib: retry bounds invalid")
        sr = n.segment_routing
        if sr.enable:
            lo, hi = sr.sr_global_range
            if not (C.MPLS_LABEL_MIN <= lo <= hi <= C.MPLS_LABEL_MAX):
                raise ConfigError("segment_routing: bad global label range")
        for p in n.originated_prefixes:
            try:
                IpPrefix.make(p.prefix)
            except ValueError as e:
                raise ConfigError(f"bad originated prefix {p.prefix!r}") from e
        pa = n.prefix_allocation
        if pa is not None:
            try:
                seed = IpPrefix.make(pa.seed_prefix)
            except ValueError as e:
                raise ConfigError(f"bad seed prefix {pa.seed_prefix!r}") from e
            if not (seed.prefix_len < pa.alloc_prefix_len <= (32 if seed.is_v4 else 128)):
                raise ConfigError(
                    "prefix_allocation: alloc_prefix_len must be within "
                    f"({seed.prefix_len}, {32 if seed.is_v4 else 128}]"
                )

    # ---- accessors --------------------------------------------------------

    @property
    def node_name(self) -> str:
        return self.node.node_name

    @property
    def areas(self) -> tuple[AreaConfig, ...]:
        return self.node.areas

    def area_ids(self) -> list[str]:
        return [a.area_id for a in self.node.areas]
