"""An overload-only change re-bases the CSR (PR 39): `LinkState.to_csr`
derives the next base from the last view with one bit of
`node_overloaded` flipped instead of walking every adjacency again. The
claim the solver rests on: the derived CSR is what `_build_csr()` of the
same LSDB gives, array for array and map for map. Seeded random sequences
on small fat trees with parallel links and a drained link mix node
overload flips (one node twice, two nodes before one `to_csr`), metric
changes left pending across a flip, and the changes that must fall back
to a build (a link's overload bit, an adjacency or a node added or
removed); snapshots are taken before and after flips."""

import dataclasses
import random

import numpy as np
import pytest

from openr_tpu.decision.linkstate import LinkState
from openr_tpu.types.topology import Adjacency
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
        if kind in ("fallback", "fallback_flip"):
            assert grew == {"builds": 1, "overload_rebases": 0}, kind
        elif kind == "metric":
            assert grew == {"builds": 0, "overload_rebases": 0}, kind
        else:
            # one re-base however many flips: a new base, an empty journal
            assert grew == {"builds": 0, "overload_rebases": 1}, kind
            assert base.version == base.base_version
            assert base.patches == ()
            assert base.base_version not in bases
        bases.add(base.base_version)
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
