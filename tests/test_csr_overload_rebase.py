"""An overload-only change re-bases the CSR (PR 39): `LinkState.to_csr`
derives the next base from the last view with one bit of
`node_overloaded` flipped instead of walking every adjacency again. The
claim the solver rests on: the derived CSR is what `_build_csr()` of the
same LSDB gives, array for array and map for map. Seeded random sequences
on small fat trees with parallel links and a drained link mix node
overload flips (one node twice, two nodes before one `to_csr`), metric
changes left pending across a flip, and the changes that must fall back
to a build (a link's overload bit, an adjacency or a node added or
removed); snapshots are taken before and after flips.

The solver keys on the same lineage: a re-based base carries its
parent's device tables (`TpuSpfSolver._carry`) with its own overload mask
placed anew, and equal routes hold whether it carries or builds. The
assembly's caches key on `CsrGraph.structure_key`, which falls back to the
base where a graph was built by hand with no `structure_version`."""

import dataclasses
import gc
import random
import weakref

import numpy as np
import pytest

from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.ops.spf import INF_DIST
from openr_tpu.types.topology import Adjacency, ForwardingAlgorithm
from openr_tpu.utils import topogen

ARRAYS = ("edge_src", "edge_dst", "edge_metric", "node_overloaded",
          "node_mask")


def fabric(k: int, rng: random.Random) -> dict:
    """A k-ary fat tree's databases, with parallel links between some
    ToR / aggregation pairs (another metric) and one link drained from
    one side."""
    adj_dbs, _ = topogen.fat_tree(k)
    dbs = {db.this_node_name: db for db in adj_dbs}
    pairs = sorted({
        tuple(sorted((db.this_node_name, a.other_node_name)))
        for db in adj_dbs for a in db.adjacencies
    })
    for u, v in rng.sample(pairs, len(pairs) // 4):
        m = rng.randint(1, 9)
        for me, other in ((u, v), (v, u)):
            extra = Adjacency(other_node_name=other, if_name=f"p_{me}_{other}",
                              other_if_name=f"p_{other}_{me}", metric=m)
            dbs[me] = dataclasses.replace(
                dbs[me], adjacencies=dbs[me].adjacencies + (extra,))
    node = rng.choice(sorted(dbs))
    dbs[node] = _with_adj(dbs[node], 0, is_overloaded=True)
    return dbs


def _with_adj(db, i: int, **change):
    adjs = list(db.adjacencies)
    adjs[i] = dataclasses.replace(adjs[i], **change)
    return dataclasses.replace(db, adjacencies=tuple(adjs))


def built(dbs: dict):
    """The reference: a fresh LinkState loaded with `dbs`, built."""
    fresh = LinkState()
    for db in dbs.values():
        fresh.update_adjacency_db(db)
    return fresh._build_csr()


def assert_same_csr(got, want):
    for name in ARRAYS:
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    assert (got.num_nodes, got.num_edges) == (want.num_nodes, want.num_edges)
    assert got.name_to_id == want.name_to_id
    assert got.edge_index == want.edge_index
    assert got.node_names == want.node_names
    assert set(got.adj_details) == set(want.adj_details)
    for key in want.edge_index:
        assert got.details(*key) == want.details(*key), key
    for a, b in zip(got.dense_tables(), want.dense_tables()):
        assert np.array_equal(a, b)


class Story:
    """One live LinkState and the databases it was given, under a
    seeded random sequence of changes."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(seed)
        self.dbs = fabric(k, self.rng)
        self.ls = LinkState()
        for db in self.dbs.values():
            self.ls.update_adjacency_db(db)
        self.removed: dict = {}  # node -> its database, while deleted

    def apply(self, db) -> None:
        self.dbs[db.this_node_name] = db
        assert self.ls.update_adjacency_db(db)

    def flip(self, node: str) -> None:
        db = self.dbs[node]
        self.apply(dataclasses.replace(db, is_overloaded=not db.is_overloaded))

    def metric(self) -> None:
        node = self.rng.choice(sorted(self.dbs))
        db = self.dbs[node]
        i = self.rng.randrange(len(db.adjacencies))
        old = db.adjacencies[i].metric
        self.apply(_with_adj(db, i, metric=self.rng.choice(
            [m for m in range(1, 12) if m != old])))

    def fallback(self) -> None:
        """One change that is neither metric-only nor overload-only."""
        kind = self.rng.choice(("link_overload", "adj", "node"))
        if kind == "node" and self.removed:
            node, db = self.removed.popitem()
            self.apply(db)
            return
        node = self.rng.choice(sorted(self.dbs))
        db = self.dbs[node]
        if kind == "link_overload":
            i = self.rng.randrange(len(db.adjacencies))
            self.apply(_with_adj(
                db, i, is_overloaded=not db.adjacencies[i].is_overloaded))
        elif (kind == "adj" and len(db.adjacencies) > 2
              and self.rng.random() < 0.5):
            self.apply(dataclasses.replace(
                db, adjacencies=db.adjacencies[:-1]))
        elif kind == "adj":  # one more parallel link, from this side only
            first = db.adjacencies[0]
            extra = dataclasses.replace(first, if_name=f"{first.if_name}+",
                                        metric=self.rng.randint(1, 9))
            self.apply(dataclasses.replace(
                db, adjacencies=db.adjacencies + (extra,)))
        else:
            self.removed[node] = self.dbs.pop(node)
            assert self.ls.delete_adjacency_db(node)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("k", [4, 6, 8])
def test_the_derived_csr_is_the_build_of_the_same_lsdb(k, seed):
    st = Story(k, seed)
    ls, stats = st.ls, st.ls.csr_stats
    assert_same_csr(ls.to_csr(), built(st.dbs))
    assert stats == {"builds": 1, "overload_rebases": 0}
    bases = {ls.to_csr().base_version}
    structure = ls.to_csr().structure_version
    kinds = set()
    # <= 2 metric entries a step over 24 steps: under the journal's
    # compaction cap (64), so only a fallback step builds
    for step in range(24):
        before = dict(stats)
        snap, snap_dbs = ls.snapshot(), dict(st.dbs)
        names = sorted(st.dbs)
        kind = st.rng.choice(
            ("flip", "flip", "two_flips", "same_twice", "metric_flip",
             "flip_metric", "metric", "fallback", "fallback_flip"))
        kinds.add(kind)
        if kind == "flip":
            st.flip(st.rng.choice(names))
        elif kind == "two_flips":
            a, b = st.rng.sample(names, 2)
            st.flip(a)
            st.flip(b)
        elif kind == "same_twice":
            node = st.rng.choice(names)
            st.flip(node)
            st.flip(node)
        elif kind == "metric_flip":  # a metric pending across the flip
            st.metric()
            st.flip(st.rng.choice(names))
        elif kind == "flip_metric":  # and one patched onto the new base
            st.flip(st.rng.choice(names))
            st.metric()
        elif kind == "metric":
            st.metric()
            st.metric()
        elif kind == "fallback":
            st.fallback()
        else:
            st.flip(st.rng.choice(names))
            st.fallback()
        # a snapshot taken after the change shares the live cell
        after = ls.snapshot()
        got = after.to_csr()
        assert_same_csr(got, built(st.dbs))
        assert ls.to_csr() is got
        grew = {key: stats[key] - before[key] for key in stats}
        base = ls._csr_cell[0]
        assert got.base_version == base.base_version
        assert got.structure_version == base.structure_version
        if kind in ("fallback", "fallback_flip"):
            assert grew == {"builds": 1, "overload_rebases": 0}, kind
            # a build is a structure of its own
            assert base.structure_version == base.base_version
            assert base.rebased_from is None
        elif kind == "metric":
            assert grew == {"builds": 0, "overload_rebases": 0}, kind
        else:
            # one re-base however many flips: a new base, an empty journal,
            # the structure of the base before it
            assert grew == {"builds": 0, "overload_rebases": 1}, kind
            assert base.version == base.base_version
            assert base.patches == ()
            assert base.base_version not in bases
            assert base.structure_version == structure
            # derived from the last base, or from the middle of a chain
            assert (base.rebased_from[0] in bases) == (
                kind not in ("two_flips", "same_twice")), kind
        bases.add(base.base_version)
        structure = base.structure_version
        # the snapshot taken before keeps its own cell, so its old mask
        assert_same_csr(snap.to_csr(), built(snap_dbs))
    assert {"flip", "fallback"} <= kinds


def test_a_snapshot_before_a_flip_keeps_the_old_mask():
    st = Story(4, 7)
    ls = st.ls
    base = ls.to_csr()
    node = next(n for n in sorted(st.dbs) if not st.dbs[n].is_overloaded)
    i = base.name_to_id[node]
    before = ls.snapshot()
    st.flip(node)
    between = ls.snapshot()
    st.flip(node)
    # the chain: two flips of one node, read from the middle, then the end
    assert between.to_csr().node_overloaded[i]
    assert not before.to_csr().node_overloaded[i]
    last = ls.to_csr()
    assert not last.node_overloaded[i]
    assert len({base.base_version, between.to_csr().base_version,
                last.base_version}) == 3
    assert last.edge_metric is base.edge_metric
    assert last.adj_details is base.adj_details
    assert ls.csr_stats == {"builds": 1, "overload_rebases": 2}
    # through the solver: each view solves with its own mask, the first
    # by a build, the two re-based ones carried down the chain, each
    # placing its own mask
    solver, ps = device_solver(), prefixes(st)
    for view in (before, between, ls, before):
        assert_routes(solver, view, ps, ROOT)
    assert solver.dev_cache_stats["uploads"] == 3
    assert solver.dev_cache_stats["carried"] == 2


ROOT = "node-0"
TABLES = {
    "split": ("base_nbr", "base_wgt", "ov_ids", "ov_nbr", "ov_wgt",
              "out_nbr", "over"),
    "dense": ("nbr", "wgt", "over"),
}


def device_solver() -> TpuSpfSolver:
    return TpuSpfSolver(native_rib="off")


def prefixes(st: Story, ksp: tuple = ()) -> PrefixState:
    """One loopback a node of the story's fabric, KSP2_ED_ECMP on those
    of `ksp`."""
    ps = PrefixState()
    for pdb in topogen.fat_tree(st.k)[1]:
        if pdb.this_node_name in ksp:
            pdb = dataclasses.replace(pdb, prefix_entries=tuple(
                dataclasses.replace(
                    e, forwarding_algorithm=ForwardingAlgorithm.KSP2_ED_ECMP)
                for e in pdb.prefix_entries))
        ps.update_prefix_db(pdb)
    return ps


def assert_routes(solver, ls, ps, me: str):
    """The device solver's RIB of `ls` is the scalar oracle's."""
    got = solver.compute_routes(ls, ps, me)
    want = oracle_compute_routes(ls, ps, me, vectorize=False)
    assert got.unicast_routes == want.unicast_routes
    assert got.mpls_routes == want.mpls_routes
    return got


def assert_fresh_tables(solver, csr) -> dict:
    """Each table set the solver holds for `csr`'s base is what a new
    solver builds for `csr`, array for array; returns them as host
    arrays."""
    sets = solver._dev[csr.base_version]["sets"]
    held = {}
    for name, dset in sets.items():
        ref = device_solver()._device_arrays(csr, name)
        assert set(dset) == set(ref), name
        for key in TABLES[name]:
            np.testing.assert_array_equal(
                np.asarray(dset[key]), np.asarray(ref[key]),
                err_msg=f"{name}.{key}")
        held[name] = {key: np.asarray(dset[key]) for key in TABLES[name]}
    return held


def patched_metric(st: Story) -> None:
    """One metric change that patches the CSR (not one on a drained
    link, which no edge carries)."""
    for _ in range(20):
        st.metric()
        if st.ls.to_csr().patches:
            return
    raise AssertionError("no metric change reached an edge")


def undrained(st: Story) -> str:
    return next(n for n in sorted(st.dbs)
                if n != ROOT and not st.dbs[n].is_overloaded)


@pytest.mark.parametrize("ksp", [(), ("node-19",)], ids=["spf", "ksp"])
def test_a_carried_base_is_a_fresh_build_and_a_patch_leaves_its_parent_alone(ksp):
    st = Story(4, 11)
    ls, ps = st.ls, prefixes(st, ksp)
    solver = device_solver()
    assert_routes(solver, ls, ps, ROOT)
    # the view the flip derives from is patched and never solved: the
    # carry first brings the parent's entry forward to it
    patched_metric(st)
    parent = ls.to_csr()
    stats = solver.dev_cache_stats
    uploads = stats["uploads"]
    st.flip(undrained(st))
    assert_routes(solver, ls, ps, ROOT)
    child = ls.to_csr()
    assert child.rebased_from == (
        parent.base_version, parent.version, parent.patches)
    assert child.structure_version == parent.structure_version
    assert child.base_version != parent.base_version
    # each set carried, its mask alone placed
    sets = 2 if ksp else 1
    assert (stats["uploads"], stats["carried"]) == (uploads + sets, sets)
    assert_fresh_tables(solver, child)
    before = assert_fresh_tables(solver, parent)
    # a metric patch of the carried base scatters into its own tables
    patched_metric(st)
    assert_routes(solver, ls, ps, ROOT)
    view = ls.to_csr()
    assert view.base_version == child.base_version and view.patches
    assert view.structure_version == parent.structure_version
    assert (stats["uploads"], stats["carried"]) == (uploads + sets, sets)
    assert_fresh_tables(solver, view)
    entry = solver._dev[parent.base_version]
    for name, arrays in before.items():
        for key, arr in arrays.items():
            np.testing.assert_array_equal(
                np.asarray(entry["sets"][name][key]), arr,
                err_msg=f"{name}.{key}")


@pytest.mark.parametrize("flips", ["two_nodes", "one_node_twice"])
def test_two_flips_with_no_solve_between_build_to_the_same_routes(flips):
    """The middle base never reached the device, so the last one has no
    parent entry to carry: it is built, to the routes a new solver
    gives."""
    st = Story(4, 5)
    ls, ps = st.ls, prefixes(st)
    solver = device_solver()
    assert_routes(solver, ls, ps, ROOT)
    a = undrained(st)
    st.flip(a)
    st.flip(a if flips == "one_node_twice" else undrained(st))
    got = assert_routes(solver, ls, ps, ROOT)
    assert ls.csr_stats["overload_rebases"] == 1
    stats = solver.dev_cache_stats
    assert (stats["uploads"], stats["carried"]) == (2, 0)
    assert_fresh_tables(solver, ls.to_csr())
    fresh = device_solver().compute_routes(ls, ps, ROOT)
    assert got.unicast_routes == fresh.unicast_routes
    assert got.mpls_routes == fresh.mpls_routes


def test_a_rebased_base_keeps_no_view_of_its_parent_alive():
    """A drain records numbers of its parent, not the parent: a chain of
    drains holds one view, not every earlier one."""
    st = Story(4, 3)
    ls, ps = st.ls, prefixes(st)
    solver = device_solver()
    patched_metric(st)
    assert_routes(solver, ls, ps, ROOT)
    parent = weakref.ref(ls.to_csr())
    assert parent() is not None
    st.flip(undrained(st))
    assert_routes(solver, ls, ps, ROOT)
    child = ls.to_csr()
    assert solver.dev_cache_stats["carried"] == 1
    gc.collect()
    assert parent() is None
    assert child.rebased_from[0] != child.base_version


def raised_link(st: Story) -> tuple[str, str]:
    """Raise every parallel link of one edge that does not leave the
    root to metric 20 (21 where it was 20); returns the edge's names."""
    csr = st.ls.to_csr()
    root = csr.name_to_id[ROOT]
    u, v = next(k for k in sorted(csr.edge_index) if k[0] != root)
    node, other = csr.node_names[u], csr.node_names[v]
    db = st.dbs[node]
    m = 21 if csr.edge_metric[csr.edge_index[(u, v)]] == 20 else 20
    st.apply(dataclasses.replace(db, adjacencies=tuple(
        dataclasses.replace(a, metric=m) if a.other_node_name == other
        else a for a in db.adjacencies)))
    after = st.ls.to_csr()
    assert after.base_version == csr.base_version
    assert after.edge_metric[after.edge_index[(u, v)]] == m
    return node, other


def test_the_cone_index_and_ksp_counts_live_and_die_with_their_base():
    """The warm start's cone index and KSP's neighbor counts are kept in
    their base's entry: a built base computes its own, an overload
    re-base carries its parent's as the same objects, a metric flap on
    the drained base warm-starts to the oracle's routes, and once the
    entry is evicted the arrays are computed again and stored nowhere."""
    st = Story(4, 9)
    ls, ps = st.ls, prefixes(st, ("node-19",))
    solver = device_solver()
    got, art = solver.compute_routes(ls, ps, ROOT, return_artifact=True)
    base = ls.to_csr()
    host = solver._dev[base.base_version]["host"]
    counts = host["ksp_counts"]
    valid = base.edge_metric < INF_DIST
    for arr, ends in zip(counts, (base.edge_src, base.edge_dst)):
        np.testing.assert_array_equal(
            arr, np.bincount(ends[valid], minlength=base.padded_nodes))
    assert "warm_out" not in host  # a warm start makes it
    pairs = {raised_link(st)}

    def warm(art, got):
        out = solver.warm_compute_routes(
            art, ls, ps, ROOT, pairs, set(), got, 1.0)
        assert out is not None
        want = oracle_compute_routes(ls, ps, ROOT, vectorize=False)
        assert out[0].unicast_routes == want.unicast_routes
        assert out[0].mpls_routes == want.mpls_routes
        return out

    warm(art, got)
    cone = host["warm_out"]
    order, row_start = cone
    assert len(order) == base.num_edges
    assert row_start[-1] == base.num_edges
    # a drain: the re-based base carries both, the same objects
    st.flip(undrained(st))
    got, art = solver.compute_routes(ls, ps, ROOT, return_artifact=True)
    drained = ls.to_csr()
    assert drained.rebased_from[0] == base.base_version
    assert solver.dev_cache_stats["carried"] == 2  # split + dense
    child = solver._dev[drained.base_version]["host"]
    assert child is not host
    assert child["ksp_counts"] is counts and child["warm_out"] is cone
    # a metric flap on the drained base: a warm start on the carried index
    pairs = {raised_link(st)}
    warm(art, got)
    assert solver.warm_solves == 2  # each walked a cone
    assert child["warm_out"] is cone and child["ksp_counts"] is counts
    # four more drains evict both entries: the index of an evicted base
    # is computed again and kept nowhere
    for _ in range(4):
        st.flip(undrained(st))
        assert_routes(solver, ls, ps, ROOT)
    assert base.base_version not in solver._dev
    assert drained.base_version not in solver._dev
    again = solver._warm_out_index(base)
    assert again is not cone and base.base_version not in solver._dev
    for a, b in zip(again, cone):
        np.testing.assert_array_equal(a, b)
    assert all(
        e["host"].get("warm_out") is not again
        for e in solver._dev.values())


def test_graphs_built_with_no_structure_version_key_on_their_base():
    """A hand-built CsrGraph (the benchmark's er100k cell builds one)
    leaves `structure_version` at 0, so `structure_key` is its
    `base_version`: two such topologies under one PrefixState, the same
    names under other ids, share no election view, MPLS class group or
    label vector, and a solver that alternates between them gives each
    the routes a fresh solver gives."""
    def lsdb(seed: int):
        return topogen.erdos_renyi_lsdb(
            48, avg_degree=4, seed=seed, max_metric=16)

    graphs = []
    for seed, reverse in ((5, False), (6, True)):
        csr = lsdb(seed)[2]
        names = csr.node_names[::-1] if reverse else csr.node_names
        csr = dataclasses.replace(
            csr, node_names=names, structure_version=0,
            name_to_id={n: i for i, n in enumerate(names)})
        graphs.append((topogen.LsdbView(csr), csr, names[0]))
    (_, a, _), (_, b, _) = graphs
    assert a.base_version != b.base_version
    assert a.structure_key == a.base_version
    assert b.structure_key == b.base_version
    ps = lsdb(5)[1]  # one loopback a name, the same names in both graphs
    solver = device_solver()
    for ls, csr, me in graphs * 2:
        got = solver.compute_routes(ls, ps, me)
        # a fresh PrefixState too: the view cell lives in the PrefixState
        want = device_solver().compute_routes(ls, lsdb(5)[1], me)
        assert got.unicast_routes == want.unicast_routes
        assert got.mpls_routes == want.mpls_routes
        assert len(got.unicast_routes) > 0 and len(got.mpls_routes) > 0
        view = ps.election_view(csr.name_to_id, csr.structure_key)
        assert view.gen[2] == csr.base_version
    bases = {a.base_version, b.base_version}
    assert {
        key for key, cell in solver._structure.items() if cell["labels"]
    } == bases
    groups = {
        gkey[0] for cell in solver._slots.values()
        for gkey in cell["mpls_cls"]["groups"]}
    assert groups == bases
