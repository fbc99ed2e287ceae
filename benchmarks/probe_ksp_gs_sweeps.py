"""Sweep-count probe behind the KSP Gauss-Seidel negative result.

Counts fixpoint sweeps of the config-4 ring-of-rings SSSP under plain
Jacobi, forward Gauss-Seidel chunking (gs=4/8/16), and
alternating-direction chunking. Measured: 73 / 71 / 69 — chunk order
cannot beat the hop-limited dependency chain (a boundary only helps
when the frontier is AT it). Full analysis:
docs/spf_kernel_profile.md, "Negative result #2".
"""

from pathlib import Path
import os
import sys

REPO = str(Path(__file__).resolve().parent.parent)
sys.path.insert(0, REPO)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import jax.numpy as jnp
import numpy as np
import importlib.util
spec = importlib.util.spec_from_file_location("bkl", REPO + "/benchmarks/bench_ksp_lfa.py")
m = importlib.util.module_from_spec(spec)
import types
sys.modules["bkl"] = m
# exec only the topology builder by importing module without main
src = open(REPO + "/benchmarks/bench_ksp_lfa.py").read()
ns = {}
ns["__file__"] = REPO + "/benchmarks/bench_ksp_lfa.py"
exec(compile(src.split("def main(")[0], "bkl", "exec"), ns)
dbs = ns["build_backbone"](128, 16)
from openr_tpu.decision.linkstate import LinkState
ls = LinkState()
for d in dbs: ls.update_adjacency_db(d)
csr = ls.to_csr()
from openr_tpu.ops.spf import build_dense_tables, INF_DIST
from openr_tpu.ops.ksp import build_ksp_blocked, _UNROLL_MAX_W
nbr, wgt = build_dense_tables(csr.edge_src, csr.edge_dst, csr.edge_metric, csr.padded_nodes)
print("tables:", nbr.shape)
n, width = nbr.shape
blocked = build_ksp_blocked(nbr, csr.node_overloaded, 0)
b = 8
dests = np.arange(1, 1 + b, dtype=np.int32) * 100

def sweeps(gs):
    csz = n // gs
    dist = jnp.full((n, b), INF_DIST, jnp.int32).at[0, :].set(0)
    usable = (~jnp.asarray(blocked)[:, :, None]) & jnp.broadcast_to(jnp.asarray(wgt)[:, :, None] < INF_DIST, (n, width, b))
    nbrj, wgtj = jnp.asarray(nbr), jnp.asarray(wgt)
    it = 0
    while True:
        dd = dist
        if gs == 1:
            acc = jnp.full((n, b), INF_DIST, jnp.int32)
            for col in range(width):
                g = dd[nbrj[:, col]]
                c = jnp.where(usable[:, col, :] & (g < INF_DIST), jnp.minimum(g + wgtj[:, col][:, None], INF_DIST), INF_DIST)
                acc = jnp.minimum(acc, c)
            new = jnp.minimum(acc, dd)
        else:
            new = dd
            for ci in range(gs):
                o = ci * csz
                acc = jnp.full((csz, b), INF_DIST, jnp.int32)
                for col in range(width):
                    g = new[nbrj[o:o+csz, col]]
                    c = jnp.where(usable[o:o+csz, col, :] & (g < INF_DIST), jnp.minimum(g + wgtj[o:o+csz, col][:, None], INF_DIST), INF_DIST)
                    acc = jnp.minimum(acc, c)
                new = new.at[o:o+csz].set(jnp.minimum(new[o:o+csz], acc))
        it += 1
        if not bool(jnp.any(new < dist)):
            break
        dist = new
        if it > n: break
    return it

for gs in (1, 4, 8, 16):
    print(f"gs={gs:2d}: {sweeps(gs)} sweeps")

def sweeps_alt(gs):
    csz = n // gs
    dist = jnp.full((n, b), INF_DIST, jnp.int32).at[0, :].set(0)
    usable = (~jnp.asarray(blocked)[:, :, None]) & jnp.broadcast_to(jnp.asarray(wgt)[:, :, None] < INF_DIST, (n, width, b))
    nbrj, wgtj = jnp.asarray(nbr), jnp.asarray(wgt)
    it = 0
    while True:
        dd = dist
        order = range(gs) if it % 2 == 0 else range(gs - 1, -1, -1)
        new = dd
        for ci in order:
            o = ci * csz
            acc = jnp.full((csz, b), INF_DIST, jnp.int32)
            for col in range(width):
                g = new[nbrj[o:o+csz, col]]
                c = jnp.where(usable[o:o+csz, col, :] & (g < INF_DIST), jnp.minimum(g + wgtj[o:o+csz, col][:, None], INF_DIST), INF_DIST)
                acc = jnp.minimum(acc, c)
            new = new.at[o:o+csz].set(jnp.minimum(new[o:o+csz], acc))
        it += 1
        if not bool(jnp.any(new < dist)):
            break
        dist = new
        if it > n: break
    return it

for gs in (4, 8, 16):
    print(f"alt gs={gs:2d}: {sweeps_alt(gs)} sweeps")
