"""Device compute kernels (JAX/XLA) — the TPU Decision hot path.

The reference's equivalent is the scalar C++ SPF core
(reference: openr/decision/LinkState.cpp † runSpf + SpfSolver †). Here it is
a batched, masked, fixed-shape JAX program; see `spf.py` and
`spf_split.py`.

Every jitted entry point lives under this package, so its import is the
one place that every JAX user of the repo passes (product, bench.py,
__graft_entry__.py, chip_smoke.py, tests): the persistent compile cache
is placed here.
"""

import os
from pathlib import Path

import jax

#: where compiled executables persist when the environment names no
#: directory. A fixed path inside the checkout: the path is part of how
#: a run finds its cache again, so nothing from tempfile, pid or time.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # set from outside → jax reads the variable itself; set nothing
    jax.config.update(
        "jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE_DIR)
    )

from openr_tpu.ops.spf import (  # noqa: E402,F401
    INF_DIST,
    batched_sssp_dense,
    build_dense_tables,
    first_hop_matrix,
)
