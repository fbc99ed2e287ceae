"""Multi-node in-process emulator (reference: openr/tests/OpenrWrapper †).

`Cluster` spins N complete OpenrNodes in one process: Spark packets run
over `MockIoHub` links, KvStore peering over `InProcKvTransport`, and
route programming into per-node `MockFibHandler`s — the reference's
multi-node-without-a-cluster testing pattern, also used by the
`python -m openr_tpu.emulator` CLI for interactive convergence demos.
"""

from openr_tpu.emulator.chaos import (  # noqa: F401
    ChaosEvent,
    ChaosFibHandler,
    ChaosIoHub,
    ChaosKvTransport,
    ChaosPlan,
    FibFaults,
    KvFaults,
    LinkFaults,
    run_schedule,
)
from openr_tpu.emulator.cluster import (  # noqa: F401
    Cluster,
    ClusterNodeSpec,
    LinkSpec,
    without_anti_entropy,
)
from openr_tpu.emulator.convergence import measure_convergence  # noqa: F401
from openr_tpu.emulator.invariants import (  # noqa: F401
    Violation,
    assert_invariants,
    check_cluster,
    dump_flight_recorders,
    wait_quiescent,
)
from openr_tpu.emulator.tracing import (  # noqa: F401
    collect_flood_traces,
    trace_report,
)
