"""Incremental (metric-only) LSDB churn tests — SURVEY §7 step 5: delta
as data, not shape. A metric-only adjacency change must patch the cached
CSR (and the solver's device-resident arrays) instead of rebuilding, and
must produce results identical to a from-scratch rebuild."""

import numpy as np
import pytest

from openr_tpu.decision.linkstate import LinkState, _metric_only_delta
from openr_tpu.types.topology import Adjacency, AdjacencyDatabase


def adj(other, ifn, metric, **kw):
    return Adjacency(
        other_node_name=other, if_name=ifn,
        other_if_name=f"to-{ifn}", metric=metric, **kw,
    )


def db(node, *adjs, overloaded=False, label=0):
    return AdjacencyDatabase(
        this_node_name=node, adjacencies=tuple(adjs),
        is_overloaded=overloaded, node_label=label,
    )


def ring_dbs(n, metric=10):
    out = []
    for i in range(n):
        l, r = (i - 1) % n, (i + 1) % n
        out.append(
            db(
                f"n{i}",
                adj(f"n{l}", f"if{i}{l}", metric),
                adj(f"n{r}", f"if{i}{r}", metric),
            )
        )
    return out


def fresh_ls(dbs):
    ls = LinkState()
    for d in dbs:
        ls.update_adjacency_db(d)
    return ls


def test_metric_only_delta_detection():
    a = db("x", adj("y", "i1", 10), adj("z", "i2", 20))
    b = db("x", adj("y", "i1", 15), adj("z", "i2", 20))
    d = _metric_only_delta(a, b)
    assert d is not None and len(d) == 1 and d[0].metric == 15
    # structural changes → None
    assert _metric_only_delta(a, db("x", adj("y", "i1", 10))) is None
    assert (
        _metric_only_delta(a, db("x", adj("y", "i1", 10), adj("w", "i2", 20)))
        is None
    )
    assert (
        _metric_only_delta(
            a, db("x", adj("y", "i1", 10), adj("z", "i2", 20), overloaded=True)
        )
        is None
    )
    assert _metric_only_delta(a, b.__class__(
        this_node_name="x",
        adjacencies=(adj("y", "i1", 10), adj("z", "i2", 20, weight=9)),
    )) is None


def test_patch_path_taken_and_matches_full_rebuild():
    dbs = ring_dbs(8)
    ls = fresh_ls(dbs)
    base = ls.to_csr()
    # metric-only change on n3→n4
    new3 = db(
        "n3", adj("n2", "if32", 10), adj("n4", "if34", 77)
    )
    assert ls.update_adjacency_db(new3)
    patched = ls.to_csr()
    # base preserved, patch journal carried
    assert patched.base_version == base.version
    assert patched.version != base.version
    assert len(patched.patches) == 1
    # equivalent to a from-scratch build
    ref = fresh_ls(dbs[:3] + [new3] + dbs[4:]).to_csr()
    np.testing.assert_array_equal(patched.edge_metric, ref.edge_metric)
    np.testing.assert_array_equal(patched.edge_src, ref.edge_src)
    np.testing.assert_array_equal(patched.edge_dst, ref.edge_dst)
    # details patched for solver nexthop construction (override layer —
    # the shared base dict itself stays untouched)
    u, w = patched.name_to_id["n3"], patched.name_to_id["n4"]
    assert patched.details(u, w)[0][1] == 77
    assert base.details(u, w)[0][1] == 10
    assert patched.adj_details[(u, w)][0][1] == 10  # base dict shared


def test_dense_tables_patched():
    dbs = ring_dbs(8)
    ls = fresh_ls(dbs)
    csr0 = ls.to_csr()
    csr0.dense_tables()  # materialize on the base
    new3 = db("n3", adj("n2", "if32", 10), adj("n4", "if34", 55))
    ls.update_adjacency_db(new3)
    patched = ls.to_csr()
    nbr, wgt = patched.dense_tables()
    ref_nbr, ref_wgt = fresh_ls(
        dbs[:3] + [new3] + dbs[4:]
    ).to_csr().dense_tables()
    np.testing.assert_array_equal(nbr, ref_nbr)
    np.testing.assert_array_equal(wgt, ref_wgt)


def test_structural_change_falls_back_to_rebuild():
    ls = fresh_ls(ring_dbs(6))
    ls.to_csr()
    # drop one adjacency: structural → rebuild
    ls.update_adjacency_db(db("n2", adj("n1", "if21", 10)))
    csr = ls.to_csr()
    assert csr.patches == ()
    assert csr.base_version == csr.version


def test_snapshot_isolation_under_patches():
    dbs = ring_dbs(6)
    ls = fresh_ls(dbs)
    ls.to_csr()
    snap = ls.snapshot()
    ls.update_adjacency_db(
        db("n0", adj("n5", "if05", 10), adj("n1", "if01", 99))
    )
    live = ls.to_csr()
    old = snap.to_csr()
    u, w = live.name_to_id["n0"], live.name_to_id["n1"]
    i = live.edge_index[(u, w)]
    assert live.edge_metric[i] == 99
    assert old.edge_metric[i] == 10


def test_repeated_patches_accumulate():
    dbs = ring_dbs(6)
    ls = fresh_ls(dbs)
    ls.to_csr()
    for m in (20, 30, 40):
        ls.update_adjacency_db(
            db("n1", adj("n0", "if10", m), adj("n2", "if12", 10))
        )
        csr = ls.to_csr()
        u, w = csr.name_to_id["n1"], csr.name_to_id["n0"]
        assert csr.edge_metric[csr.edge_index[(u, w)]] == m
    # journal is cumulative against one base
    assert csr.base_version != csr.version
    ref = fresh_ls(
        [db("n1", adj("n0", "if10", 40), adj("n2", "if12", 10))]
        + [d for d in dbs if d.this_node_name != "n1"]
    ).to_csr()
    np.testing.assert_array_equal(csr.edge_metric, ref.edge_metric)


def _prefix_state(ls, ksp_nodes=()):
    """One /32 per node; KSP2_ED_ECMP on those of `ksp_nodes`."""
    from openr_tpu.decision.linkstate import PrefixState
    from openr_tpu.types.topology import (
        ForwardingAlgorithm,
        PrefixDatabase,
        PrefixEntry,
    )

    ps = PrefixState()
    for i, name in enumerate(ls.nodes):
        algo = (
            ForwardingAlgorithm.KSP2_ED_ECMP
            if name in ksp_nodes
            else ForwardingAlgorithm.SP_ECMP
        )
        ps.update_prefix_db(
            PrefixDatabase(
                this_node_name=name,
                prefix_entries=(
                    PrefixEntry(
                        prefix=f"10.7.{i}.1/32", forwarding_algorithm=algo
                    ),
                ),
            )
        )
    return ps


def test_solver_device_cache_incremental():
    """TpuSpfSolver distances after a device-side patch == a fresh
    solver's distances on the same topology == the reference solve on
    its full-width tables, and its RIB == the oracle's."""
    import jax.numpy as jnp

    from openr_tpu.decision.oracle import compute_routes as oracle_routes
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.ops.spf import batched_sssp_dense, pad_batch

    dbs = ring_dbs(8)
    ls = fresh_ls(dbs)
    solver = TpuSpfSolver(native_rib="off")
    csr = ls.to_csr()
    n = csr.num_nodes
    # root at n3 so the n3→n4 metric bump changes its own distances
    roots = np.full(pad_batch(4), csr.name_to_id["n3"], dtype=np.int32)
    d0 = np.asarray(solver._solve_dist(csr, roots))
    ls2 = ls.snapshot()
    ls2.update_adjacency_db(
        db("n3", adj("n2", "if32", 10), adj("n4", "if34", 70))
    )
    # reverse direction so the bidirectional metric changes too
    csr2 = ls2.to_csr()
    assert csr2.patches, "patch path not taken"
    d1 = np.asarray(solver._solve_dist(csr2, roots))
    fresh = TpuSpfSolver(native_rib="off")
    d_ref = np.asarray(fresh._solve_dist(csr2, roots))
    np.testing.assert_array_equal(d1, d_ref)
    nbr, wgt = csr2.dense_tables()
    d_plain = np.asarray(
        batched_sssp_dense(
            jnp.asarray(nbr), jnp.asarray(wgt),
            jnp.asarray(csr2.node_overloaded), jnp.asarray(roots),
            has_overloads=False,
        )
    )
    np.testing.assert_array_equal(d1[:n], d_plain[:n])
    assert (d1 != d0).any()  # the metric change actually moved dists
    ps = _prefix_state(ls2)
    got = solver.compute_routes(ls2, ps, "n3")
    want = oracle_routes(ls2, ps, "n3")
    assert got.unicast_routes == want.unicast_routes
    assert got.mpls_routes == want.mpls_routes
    # and solving the ORIGINAL snapshot again still works (backward
    # version → full re-upload, not corruption)
    d_back = np.asarray(solver._solve_dist(csr, roots))
    np.testing.assert_array_equal(d_back, d0)


@pytest.mark.parametrize(
    "ksp_nodes,want_sets",
    [((), {"split"}), (("n6",), {"split", "dense"})],
    ids=["ksp_off", "one_ksp_prefix"],
)
def test_device_cache_holds_only_sets_asked_for(ksp_nodes, want_sets):
    """After a metric patch the device cache holds exactly the table
    sets a solve asked for — the split tables, and KSP's full-width
    tables only once a KSP prefix wants them — and each, patched in
    place, equals a fresh upload of the patched topology."""
    from openr_tpu.decision.spf_backend import TpuSpfSolver

    ls = fresh_ls(ring_dbs(8))
    ps = _prefix_state(ls, ksp_nodes)
    solver = TpuSpfSolver(native_rib="off")
    solver.compute_routes(ls, ps, "n0")
    ls.update_adjacency_db(
        db("n3", adj("n2", "if32", 10), adj("n4", "if34", 70))
    )
    csr = ls.to_csr()
    assert csr.patches, "patch path not taken"
    solver.compute_routes(ls, ps, "n0")
    assert solver.dev_cache_stats["patches"] == 1
    sets = solver._dev[csr.base_version]["sets"]
    assert set(sets) == want_sets
    fresh = TpuSpfSolver(native_rib="off")
    for name, dset in sets.items():
        ref = fresh._device_arrays(csr, name)
        assert set(dset) == set(ref)
        for key, arr in dset.items():
            np.testing.assert_array_equal(
                np.asarray(arr), np.asarray(ref[key]), err_msg=f"{name}.{key}"
            )
    assert fresh.dev_cache_stats["patches"] == 0


def test_decision_churn_end_to_end_equivalence():
    """Decision's full RIB under metric churn equals a from-scratch
    compute — through the real publication path."""
    from openr_tpu.config import Config
    from openr_tpu.decision.decision import Decision
    from openr_tpu.messaging import ReplicateQueue
    from openr_tpu.types.kvstore import Publication, Value
    from openr_tpu.types.serde import to_wire

    def mk_decision():
        cfg = Config.default("n0")
        q = ReplicateQueue(name="pubs")
        routes = ReplicateQueue(name="routes")
        return Decision(cfg, q.get_reader("d"), routes, solver="tpu")

    def pub_for(d, db_):
        return Publication(
            area="0",
            key_vals={
                f"adj:{db_.this_node_name}": Value(
                    version=1, originator_id=db_.this_node_name,
                    value=to_wire(db_),
                ).with_hash()
            },
        )

    dbs = ring_dbs(8)
    dec = mk_decision()
    for d in dbs:
        dec.process_publication(pub_for(dec, d))
    rib0 = dec.compute_rib()

    churned = db("n5", adj("n4", "if54", 10), adj("n6", "if56", 33))
    dec.process_publication(pub_for(dec, churned))
    rib1 = dec.compute_rib()

    dec_fresh = mk_decision()
    for d in dbs[:5] + [churned] + dbs[6:]:
        dec_fresh.process_publication(pub_for(dec_fresh, d))
    rib_ref = dec_fresh.compute_rib()
    assert rib1.unicast_routes == rib_ref.unicast_routes
    assert rib1.mpls_routes == rib_ref.mpls_routes


def test_device_cache_zero_reuploads_under_metric_churn():
    """Under sustained metric-only churn — including KSP-bearing
    rebuilds — the solver's device cache must absorb every update as a
    patch scatter: ZERO table re-uploads after warmup (round-2 verdict
    item 4's done-criterion)."""
    import dataclasses

    from openr_tpu.decision.linkstate import PrefixState
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.types.topology import (
        ForwardingAlgorithm,
        PrefixDatabase,
    )
    from openr_tpu.utils import topogen

    adj_dbs, prefix_dbs = topogen.grid(4, 4)
    ls = fresh_ls(adj_dbs)
    ps = PrefixState()
    for i, p in enumerate(prefix_dbs):
        entries = tuple(
            dataclasses.replace(
                e, forwarding_algorithm=ForwardingAlgorithm.KSP2_ED_ECMP
            )
            if i % 4 == 0
            else e
            for e in p.prefix_entries
        )
        ps.update_prefix_db(
            PrefixDatabase(
                this_node_name=p.this_node_name,
                prefix_entries=entries,
                area=p.area,
            )
        )
    solver = TpuSpfSolver(native_rib="off")
    solver.compute_routes(ls, ps, "node-0")  # warm: uploads happen here
    uploads_warm = solver.dev_cache_stats["uploads"]
    for m in (11, 13, 17, 19):
        base = adj_dbs[5]
        adjs = tuple(
            dataclasses.replace(a, metric=m) for a in base.adjacencies
        )
        ls.update_adjacency_db(
            dataclasses.replace(base, adjacencies=adjs)
        )
        solver.compute_routes(ls, ps, "node-0")
    stats = solver.dev_cache_stats
    assert stats["uploads"] == uploads_warm, stats  # zero re-uploads
    assert stats["patches"] >= 4, stats  # every churn step patched


def test_randomized_churn_cache_equivalence_property():
    """Property test for the cross-rebuild assembly caches: a SHARED
    solver (entry/class-dict/device caches carried across rebuilds)
    must match the stateless oracle after every step of a random
    mutation sequence — metric flaps, prefix withdraw/re-add, overload
    toggles, and adjacency removal/restore."""
    import dataclasses

    import numpy as np

    from openr_tpu.decision.linkstate import PrefixState
    from openr_tpu.decision.oracle import (
        compute_routes as oracle_compute_routes,
    )
    from openr_tpu.decision.spf_backend import TpuSpfSolver
    from openr_tpu.types.network import IpPrefix
    from openr_tpu.types.topology import PrefixDatabase, PrefixEntry
    from openr_tpu.utils import topogen

    adj_dbs, prefix_dbs = topogen.fat_tree(8)  # 80 nodes, rich ECMP
    ls = fresh_ls(adj_dbs)
    ps = PrefixState()
    for pdb in prefix_dbs:
        ps.update_prefix_db(pdb)
    rng = np.random.default_rng(99)
    solver = TpuSpfSolver(native_rib="off")
    names = [adb.this_node_name for adb in adj_dbs]
    removed: dict[str, object] = {}

    for step in range(24):
        op = rng.integers(0, 10)
        node = names[int(rng.integers(0, len(names)))]
        db = ls.adjacency_db(node)
        if op < 5 and db and db.adjacencies:
            # metric flap (the journal/patch fast path)
            adjs = list(db.adjacencies)
            k = int(rng.integers(0, len(adjs)))
            adjs[k] = dataclasses.replace(
                adjs[k], metric=int(rng.integers(1, 32))
            )
            ls.update_adjacency_db(
                dataclasses.replace(db, adjacencies=tuple(adjs))
            )
        elif op < 7:
            # prefix withdraw or re-add (solver_view gen transitions)
            i = int(rng.integers(0, len(names)))
            pfx = IpPrefix(prefix=f"10.9.{i}.0/24")
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
            # node overload toggle (structural: full CSR rebuild)
            ls.update_adjacency_db(
                dataclasses.replace(db, is_overloaded=not db.is_overloaded)
            )
        elif op < 9 and db and node not in removed and node != names[0]:
            removed[node] = db
            ls.delete_adjacency_db(node)
        elif removed:
            name, db_r = removed.popitem()
            ls.update_adjacency_db(db_r)

        got = solver.compute_routes(ls, ps, names[0])
        want = oracle_compute_routes(ls, ps, names[0])
        assert got.unicast_routes == want.unicast_routes, f"step {step}"
        assert got.mpls_routes == want.mpls_routes, f"step {step}"


def test_patch_progress_shared_across_snapshots():
    """Round-5 regression guard: the incremental patch state must live
    in the snapshot-SHARED CSR cell — when it lived on the LinkState
    instance, every per-rebuild snapshot re-applied the WHOLE
    accumulated flap backlog (O(epoch) host work per rebuild, the
    dominant config-5 cost). A later snapshot must continue from the
    progress an earlier snapshot's to_csr published."""
    import dataclasses

    from openr_tpu.decision.linkstate import LinkState

    dbs = ring_dbs(8)
    ls = fresh_ls(dbs)
    ls.to_csr()  # build the base into the shared cell

    calls = []
    orig = LinkState._apply_pending

    def spy(self, base, pending):
        calls.append(len(pending))
        return orig(self, base, pending)

    LinkState._apply_pending = spy
    try:
        for cycle in range(3):
            # two metric-only flaps per cycle
            for j in (2, 5):
                node = f"n{j}"
                cur = ls.adjacency_db(node)
                adjs = list(cur.adjacencies)
                adjs[0] = dataclasses.replace(
                    adjs[0], metric=10 + cycle + j
                )
                assert ls.update_adjacency_db(
                    dataclasses.replace(cur, adjacencies=tuple(adjs))
                )
            # the production flow: a FRESH snapshot per rebuild
            snap = ls.snapshot()
            snap.to_csr()
    finally:
        LinkState._apply_pending = orig

    # every cycle must apply ONLY its own suffix (2 flaps), never the
    # accumulated backlog (2, then 4, then 6 would indicate the r3 bug)
    assert calls == [2, 2, 2], calls
    # and the live object's shared cell carries the progress
    assert ls._csr_cell[2] == 6
