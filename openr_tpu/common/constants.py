"""Protocol constants and key conventions.

Equivalent of the reference's single constants header
(reference: openr/common/Constants.h † — all timer defaults, key prefixes,
port numbers live in one place there too).
"""

from __future__ import annotations

# ---- KvStore key conventions (reference: Constants.h † kAdjDbMarker,
# kPrefixDbMarker) -----------------------------------------------------------
ADJ_DB_MARKER = "adj:"
PREFIX_DB_MARKER = "prefix:"
KEY_DELIMITER = ":"

# ---- Default ports (reference: Constants.h † kOpenrCtrlPort etc.) ----------
CTRL_PORT = 2018  # OpenrCtrl thrift port upstream; our ctrl RPC port
KVSTORE_PORT = 2019  # our KvStore peer TCP port (upstream shares ctrl port)
SPARK_MCAST_PORT = 6666  # Spark UDP port (upstream kSparkMcastPort)

# ---- Spark timers, ms (reference: SparkConfig in OpenrConfig.thrift †) -----
SPARK_HELLO_INTERVAL_MS = 500
SPARK_FASTINIT_HELLO_INTERVAL_MS = 100
SPARK_HANDSHAKE_INTERVAL_MS = 500
SPARK_HEARTBEAT_INTERVAL_MS = 500
SPARK_HOLD_TIME_MS = 2_000
SPARK_GR_HOLD_TIME_MS = 30_000

# ---- KvStore (reference: KvstoreConfig †) ----------------------------------
KVSTORE_DEFAULT_TTL_MS = 300_000  # key_ttl_ms
KVSTORE_TTL_DECREMENT_MS = 1  # min decrement applied when flooding
KVSTORE_SYNC_INTERVAL_S = 60  # anti-entropy full-sync cadence
KVSTORE_FLOOD_RATE_MSGS_PER_SEC = 600
KVSTORE_FLOOD_RATE_BURST = 300
KVSTORE_FLOOD_PENDING_MAX_KEYS = 8192
# per-reader depth cap on the policied inter-module queues (messaging
# overload control; 0 = unbounded)
QUEUE_MAXSIZE = 1024
# Spark per-node inbox cap in the mock/UDP IO providers (a partitioned
# or stalled receiver sheds oldest packets instead of growing RAM)
SPARK_INBOX_MAXSIZE = 2048
TTL_REFRESH_FRACTION = 0.25  # originator refreshes at ttl * fraction left

# ---- Decision debounce (reference: DecisionConfig † debounce_min/max_ms) ---
DECISION_DEBOUNCE_MIN_MS = 10
DECISION_DEBOUNCE_MAX_MS = 250

# ---- LinkMonitor (reference: LinkMonitorConfig †) --------------------------
LINK_FLAP_INITIAL_BACKOFF_MS = 60
LINK_FLAP_MAX_BACKOFF_MS = 300_000
ADJACENCY_THROTTLE_MS = 1_000

# ---- Fib (reference: openr/fib/Fib.cpp † retry constants) ------------------
FIB_INITIAL_RETRY_MS = 8
FIB_MAX_RETRY_MS = 4_096
FIB_SYNC_INTERVAL_S = 60

# ---- SR-MPLS label spaces (reference: Constants.h † label ranges) ----------
MPLS_LABEL_MIN = 16
MPLS_LABEL_MAX = (1 << 20) - 1
SR_GLOBAL_RANGE = (101, 49_999)  # node segment labels
SR_LOCAL_RANGE = (50_000, 59_999)  # adjacency labels

# ---- Misc ------------------------------------------------------------------
DEFAULT_AREA = "0"

# Solver numeric contract (shared by the CSR builder, the TPU kernel, and
# the oracle): int32 distances with INF sentinel 2^30. Valid metrics are
# clamped to METRIC_MAX = 2^30-1 (covers the reference's practical metric
# range incl. RTT-us); the relax step computes min(dist + metric, INF)
# guarded by dist < INF, so the sum is at most (2^30-1) + 2^30 = 2^31-1 ==
# INT32_MAX — no wraparound. (uint32 would allow one more bit; it hung
# the TPU backend of the first bring-up and has not been re-tested on
# the v5e since.) Path costs saturate at INF (treated as unreachable);
# the oracle applies the identical clamp and saturation so RIB equality
# is exact.
DIST_INF = 1 << 30
METRIC_MAX = (1 << 30) - 1

# ---- FIB client ids (reference: openr/if/Platform.thrift † FibClient) ------
# Namespaces FibService tables between routing daemons / tools. On the
# netlink backend each client maps to its own rtproto (openr: 99,
# static/manual: the kernel's RTPROT_STATIC=4), so separation holds on
# the real kernel too, not just in the mock.
FIB_CLIENT_OPENR = 786
FIB_CLIENT_STATIC = 64

# ---- Watchdog (reference: openr/watchdog/Watchdog.cpp †) -------------------
WATCHDOG_INTERVAL_S = 20
WATCHDOG_THREAD_TIMEOUT_S = 300


def adj_key(node: str) -> str:
    """`adj:<node>` (reference: LinkMonitor advertiseAdjacencies †)."""
    return f"{ADJ_DB_MARKER}{node}"


def validate_name(name: str, what: str = "name") -> str:
    """Node/area names must not contain the key delimiter — the key format
    would be ambiguous (the reference restricts node names the same way)."""
    if KEY_DELIMITER in name or not name:
        raise ValueError(f"invalid {what} {name!r}: empty or contains ':'")
    return name


def prefix_key(node: str, area: str, prefix: str) -> str:
    """Per-prefix key `prefix:<node>:<area>:[<prefix>]`
    (reference: openr/common/LsdbUtil † createPrefixKey)."""
    validate_name(node, "node name")
    validate_name(area, "area")
    return f"{PREFIX_DB_MARKER}{node}{KEY_DELIMITER}{area}{KEY_DELIMITER}[{prefix}]"


def parse_adj_key(key: str) -> str | None:
    """Return node name if `key` is an adj key, else None."""
    if key.startswith(ADJ_DB_MARKER):
        return key[len(ADJ_DB_MARKER):]
    return None


def parse_prefix_key(key: str) -> tuple[str, str, str] | None:
    """Return (node, area, prefix) if `key` is a per-prefix key, else None."""
    if not key.startswith(PREFIX_DB_MARKER):
        return None
    rest = key[len(PREFIX_DB_MARKER):]
    try:
        node, area, bracketed = rest.split(KEY_DELIMITER, 2)
    except ValueError:
        return None
    if bracketed.startswith("[") and bracketed.endswith("]"):
        return node, area, bracketed[1:-1]
    return None
