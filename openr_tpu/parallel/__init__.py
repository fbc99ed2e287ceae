"""Multi-chip parallelism: device meshes and sharded SPF.

The reference's "distribution" is process-level across routers (its
compute is single-threaded per node — SURVEY §2). The TPU rebuild adds the
axis the reference never had: sharding one node's (or the emulator fleet's)
SPF compute across TPU cores —

  * ``sources`` axis — batch of SPF roots, embarrassingly parallel (the
    "data parallel" axis; scales all-sources SSSP and per-node fleets).
  * ``graph`` axis — the in-neighbor table rows partitioned across
    devices, with a tiled ICI `all_gather` exchanging relaxed distances
    each sweep (the "model parallel" axis; scales LSDBs beyond one chip's
    HBM).

Collectives ride ICI inside `shard_map`; over DCN, `jax.distributed`
initialises the same mesh across hosts (see `mesh.py`).
"""

from openr_tpu.parallel.mesh import make_mesh  # noqa: F401
from openr_tpu.parallel.sharded_spf import sharded_sssp_split  # noqa: F401
