"""Randomized-churn oracle-equivalence soak across graph families.

Burn-in confidence harness (SURVEY §4 test strategy: the oracle is the
ground truth; upstream's DecisionTest churn scenarios † are the model):
for each topology family, apply a random mutation stream — metric
flaps, prefix withdraw/re-add, overload toggles, adjacency
removal/restore — and after EVERY step assert that BOTH production
engines (the batched split-kernel solver and the native C++ radix-heap
engine) produce a RIB identical to the stateless python oracle.

This generalizes tests/test_incremental.py's 24-step property test to
arbitrary step counts, seeds, and families for out-of-CI burn-ins:

    python benchmarks/soak_oracle.py --steps 300 --seed 7

Exit code 0 and one PASS line per family, or a first-failure dump.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def _families():
    from openr_tpu.utils import topogen

    return {
        # name -> (adj_dbs, prefix_dbs) thunk; sizes kept oracle-sized
        "fat_tree_8": lambda: topogen.fat_tree(8),
        "fat_tree_4_hop": lambda: topogen.fat_tree(4),  # uniform metrics
        "grid_9x9": lambda: topogen.grid(9, 9),
        "ring_64": lambda: topogen.ring(64),
        "full_mesh_24": lambda: topogen.full_mesh(24),
    }


def soak_family(name: str, mk, steps: int, seed: int) -> None:
    from openr_tpu.decision.linkstate import LinkState, PrefixState
    from openr_tpu.decision.oracle import (
        compute_routes as oracle_compute_routes,
    )
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.ops.native_spf import native_available
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import PrefixDatabase, PrefixEntry

    adj_dbs, prefix_dbs = mk()
    ls = LinkState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    ps = PrefixState()
    for pdb in prefix_dbs:
        ps.update_prefix_db(pdb)

    rng = np.random.default_rng(seed)
    engines = {"split": TpuSpfSolver(native_rib="off")}
    if native_available():
        engines["native"] = TpuSpfSolver(native_rib="on")
    names = [adb.this_node_name for adb in adj_dbs]
    removed: dict[str, object] = {}
    t0 = time.perf_counter()

    for step in range(steps):
        op = rng.integers(0, 10)
        node = names[int(rng.integers(0, len(names)))]
        db = ls.adjacency_db(node)
        if op < 5 and db and db.adjacencies:
            adjs = list(db.adjacencies)
            k = int(rng.integers(0, len(adjs)))
            adjs[k] = dataclasses.replace(
                adjs[k], metric=int(rng.integers(1, 32))
            )
            ls.update_adjacency_db(
                dataclasses.replace(db, adjacencies=tuple(adjs))
            )
        elif op < 7:
            i = int(rng.integers(0, len(names)))
            pfx = IpPrefix(prefix=f"10.99.{i % 256}.0/24")
            if rng.integers(0, 2):
                ps.update_prefix_db(
                    PrefixDatabase(
                        this_node_name=names[i],
                        prefix_entries=(PrefixEntry(prefix=pfx),),
                    )
                )
            else:
                ps.withdraw(names[i], pfx)
        elif op < 8 and db:
            ls.update_adjacency_db(
                dataclasses.replace(db, is_overloaded=not db.is_overloaded)
            )
        elif op < 9 and db and node not in removed and node != names[0]:
            removed[node] = db
            ls.delete_adjacency_db(node)
        elif removed:
            nm, db_r = removed.popitem()
            ls.update_adjacency_db(db_r)

        # rotate the computing root so first-hop logic is exercised
        # from many vantage points, not just node 0
        root = names[step % min(len(names), 17)]
        if ls.adjacency_db(root) is None:
            root = names[0]
        want = oracle_compute_routes(ls, ps, root)
        for ename, solver in engines.items():
            got = solver.compute_routes(ls, ps, root)
            if (
                got.unicast_routes != want.unicast_routes
                or got.mpls_routes != want.mpls_routes
            ):
                print(
                    f"FAIL {name} step {step} engine {ename} root {root} "
                    f"seed {seed}",
                    flush=True,
                )
                uni_d = {
                    k: (
                        got.unicast_routes.get(k),
                        want.unicast_routes.get(k),
                    )
                    for k in set(got.unicast_routes) ^ set(want.unicast_routes)
                    | {
                        k
                        for k in set(got.unicast_routes)
                        & set(want.unicast_routes)
                        if got.unicast_routes[k] != want.unicast_routes[k]
                    }
                }
                for k, (g, w) in list(uni_d.items())[:5]:
                    print(f"  {k}: got={g}\n     want={w}", flush=True)
                sys.exit(1)
    dt = time.perf_counter() - t0
    print(
        f"PASS {name}: {steps} steps x {len(engines)} engines "
        f"({', '.join(engines)}) vs oracle, {dt:.1f}s",
        flush=True,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--family", default=None, help="run one family only")
    ap.add_argument(
        "--tpu",
        action="store_true",
        help="run on jax's default backend (the chip, where there is "
        "one); the soak is a CPU correctness harness by default",
    )
    args = ap.parse_args()

    if not args.tpu:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported

    fams = _families()
    if args.family:
        fams = {args.family: fams[args.family]}
    for name, mk in fams.items():
        soak_family(name, mk, args.steps, args.seed)
    print("ALL FAMILIES PASS", flush=True)


if __name__ == "__main__":
    main()
