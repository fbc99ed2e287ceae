"""A warm start scopes the complex prefixes by their advertisers (PR 37).

`warm_compute_routes` used to put every entry of `view.complex_items`
into `touched`; now `build_elect_view` gives the view a `ComplexTable`
(the known advertisers of the complex items, columnar, and the items
that hold a KSP2_ED_ECMP entry), and a complex prefix is re-elected when
one of its known advertisers is among the changed nodes, when it is
dirty, or when it is KSP. Exactness is the guarantee: every result here
is held to a from-scratch scalar oracle over the same LSDB.

  (a) the twin's fabric (`tiny_fabric_vips`) with a complex item of every
      kind beside its own VIPs, a seeded sequence of raises and restores
      through `warm_compute_routes` itself;
  (b) a KSP item and an item with one KSP advertiser among plain ones are
      in `touched` on every round, whatever moved;
  (c) `build_elect_view`'s table: slots, `seg`, unknown advertisers
      absent, `whole_graph`;
  (d) a window whose flap was fully reverted (`changes` empty) touches
      the dirt and the KSP items alone.
"""

import dataclasses
import json
import random
from pathlib import Path

import numpy as np
import pytest

from openr_tpu.decision.election import build_elect_view
from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.types.network import IpPrefix
from openr_tpu.types.topology import (
    ForwardingAlgorithm,
    PrefixDatabase,
    PrefixEntry,
    PrefixMetrics,
)
from perfbench import topo
from perfbench.drivers.decision_fib_vips import program_dbs

KSP = ForwardingAlgorithm.KSP2_ED_ECMP
GHOST = "ghost"  # advertises, and is in no adjacency database


def twin_graph() -> topo.Graph:
    spec = Path(__file__).resolve().parents[1] / "perfbench" / "configs"
    return topo.build(
        json.loads((spec / "tiny_fabric_vips.json").read_text())["topology"])


def kinds(g: topo.Graph) -> dict[str, tuple[IpPrefix, dict]]:
    """kind -> (prefix, {advertiser: PrefixEntry fields}): one complex item
    of every kind, hand-placed on the twin's ToRs (the root is ToR (0, 0))."""
    def tor(pod, i):
        return topo.node_name(topo.fat_tree_tor(g, pod, i))

    worse = PrefixMetrics(source_preference=50)
    placed = {
        "weighted": {tor(1, 0): {"weight": 2}, tor(2, 1): {"weight": 5}},
        "partly_weighted": {tor(1, 0): {}, tor(3, 0): {"weight": 3}},
        # two planes: one uplink of ToR (1, 1) raised leaves one next hop
        "min_nexthop": {tor(1, 1): {"min_nexthop": 2}},
        "min_nexthop_weighted": {
            tor(2, 0): {"min_nexthop": 2, "weight": 4},
            tor(0, 1): {"min_nexthop": 2, "weight": 1, "metrics": worse}},
        "mixed_metrics": {
            tor(2, 0): {"weight": 3},
            tor(3, 1): {"weight": 1, "metrics": worse}},
        "unknown_alone": {GHOST: {}},
        "unknown_among": {GHOST: {"weight": 2}, tor(3, 0): {"weight": 4}},
        "roots_own": {tor(0, 0): {"weight": 3}, tor(2, 1): {"weight": 2}},
        "ksp": {tor(3, 1): {"forwarding_algorithm": KSP}},
        "ksp_among_plain": {
            tor(1, 0): {}, tor(2, 0): {"forwarding_algorithm": KSP}},
    }
    return {
        kind: (IpPrefix.make(f"10.250.{n}.0/24"), per_node)
        for n, (kind, per_node) in enumerate(placed.items())
    }


def lsdb_with_kinds(g: topo.Graph):
    """The twin's databases, every advertiser's extended by the hand-placed
    items, applied directly."""
    adj_dbs, prefix_dbs = program_dbs(g)
    more: dict[str, list] = {}
    for prefix, per_node in kinds(g).values():
        for node, fields in per_node.items():
            more.setdefault(node, []).append(PrefixEntry(prefix=prefix, **fields))
    ls, ps = LinkState(), PrefixState()
    for db in adj_dbs:
        ls.update_adjacency_db(db)
    for db in prefix_dbs:
        ps.update_prefix_db(dataclasses.replace(db, prefix_entries=(
            *db.prefix_entries, *more.pop(db.this_node_name, ()))))
    assert set(more) == {GHOST}
    ps.update_prefix_db(PrefixDatabase(
        this_node_name=GHOST, prefix_entries=tuple(more[GHOST])))
    return adj_dbs, ls, ps


def links_off_the_root(g: topo.Graph, root: int) -> list[tuple[int, int]]:
    """Every link of the fabric that does not end at the root, (agg, ToR)
    or (core, agg)."""
    n_core, n_agg, half = g.meta["n_core"], g.meta["n_agg"], g.meta["half"]
    pods = n_agg // half
    out = [(topo.fat_tree_agg(g, p, i), topo.fat_tree_tor(g, p, j))
           for p in range(pods) for i in range(half) for j in range(half)]
    out += [(i * half + c, topo.fat_tree_agg(g, p, i))
            for p in range(pods) for i in range(half) for c in range(half)]
    assert max(c for c, _a in out[-pods * half * half:]) == n_core - 1
    return [(u, v) for u, v in out if root not in (u, v)]


def set_link(adj_dbs, ls: LinkState, u: int, v: int, metric: int) -> list:
    """Both ends' adjacency databases with the link at `metric`, applied;
    the dirt pairs Decision's classifier would hand the warm start."""
    pairs = []
    for a, b in ((u, v), (v, u)):
        db, other = adj_dbs[a], topo.node_name(b)
        adj_dbs[a] = dataclasses.replace(db, adjacencies=tuple(
            dataclasses.replace(x, metric=metric)
            if x.other_node_name == other else x for x in db.adjacencies))
        changed, delta = ls.update_adjacency_db_delta(adj_dbs[a])
        assert changed and delta is not None
        pairs += delta
    return pairs


def changed_nodes(art_old, art_new) -> set[str]:
    """Nodes whose (distance, first-hop set) differs between two solves,
    read off the artifacts."""
    csr, dist0, fh0, _n, _l = art_old.solved
    _c, dist1, fh1, _n, _l = art_new.solved
    n = len(csr.node_names)
    moved = (np.asarray(dist0[:, 0])[:n] != np.asarray(dist1[:, 0])[:n]) | (
        fh0[:, :n] != fh1[:, :n]).any(axis=0)
    return {csr.node_names[i] for i in np.nonzero(moved)[0]}


class Fabric:
    """The twin with the hand-placed items, solved once by the backend."""

    def __init__(self):
        self.g = twin_graph()
        self.root = topo.fat_tree_tor(self.g, 0, 0)
        self.me = topo.node_name(self.root)
        self.adj_dbs, self.ls, self.ps = lsdb_with_kinds(self.g)
        self.kinds = kinds(self.g)
        self.solver = TpuSpfSolver(native_rib="off")
        self.rdb, self.art = self.solver.compute_routes(
            self.ls, self.ps, self.me, return_artifact=True)
        self.ksp_items = {self.kinds["ksp"][0], self.kinds["ksp_among_plain"][0]}

    def warm(self, pairs, dirt=frozenset()):
        """One warm start; returns (touched, changed node names, the
        previous RIB) and holds the result to the scalar oracle."""
        before, prev, art0 = dict(self.solver.spf_kernel_stats), self.rdb, self.art
        res = self.solver.warm_compute_routes(
            self.art, self.ls, self.ps, self.me, pairs, set(dirt), self.rdb, 0.25)
        assert res is not None
        self.rdb, self.art, touched, _labels, _region = res
        oracle = oracle_compute_routes(self.ls, self.ps, self.me, vectorize=False)
        assert self.rdb.unicast_routes == oracle.unicast_routes
        assert self.rdb.mpls_routes == oracle.mpls_routes
        self.grew = {k: v - before[k] for k, v in self.solver.spf_kernel_stats.items()
                     if isinstance(v, int)}
        return touched, changed_nodes(art0, self.art), prev

    def complex_prefixes(self) -> dict:
        """Every complex item of the view -> its advertisers' names."""
        csr = self.ls.to_csr()
        view = self.ps.election_view(csr.name_to_id, csr.base_version)
        return {p: set(per_node) for p, per_node in view.complex_items}


# ------------------------------------------------------------------ (a), (b)


@pytest.mark.parametrize("seed", [37, 1037, 4100000137])
def test_a_seeded_flap_sequence_is_the_scalar_oracle_and_scoped_by_advertiser(seed):
    f = Fabric()
    rng = random.Random(seed)
    pool = links_off_the_root(f.g, f.root)
    # the uplinks of ToR (1, 1) first and last, so that every seed sees
    # `min_nexthop` suppress the route and give it back
    t11 = topo.fat_tree_tor(f.g, 1, 1)
    forced = (topo.fat_tree_agg(f.g, 1, 0), t11)
    raised: dict[tuple, int] = {}
    steps = [forced] + [rng.choice(pool) for _ in range(28)]
    p_min = f.kinds["min_nexthop"][0]
    min_nexthop_states = {p_min in f.rdb.unicast_routes}
    assert f.rdb.unicast_routes[p_min].best_entry.min_nexthop == 2
    rounds = 0
    for link in steps + sorted(set(raised) | {forced}):
        metric = 1 if link in raised else rng.choice((2, 10))
        if metric == 1:
            del raised[link]
        else:
            raised[link] = metric
        touched, changed, prev = f.warm(set_link(f.adj_dbs, f.ls, *link, metric))
        rounds += 1
        min_nexthop_states.add(p_min in f.rdb.unicast_routes)
        # (b) KSP needs the whole graph: in, whatever moved
        assert f.ksp_items <= touched
        # the scope: a complex item is named by a changed advertiser
        items = f.complex_prefixes()
        want = {p for p, advs in items.items() if advs & changed} | f.ksp_items
        assert touched & set(items) == want
        assert f.grew["complex_scoped"] == len(want) - len(f.ksp_items)
        # what the scope left alone is the cached object, not a rebuilt equal
        for p in set(items) - want:
            assert f.rdb.unicast_routes.get(p) is prev.unicast_routes.get(p)
    assert rounds >= 20
    assert min_nexthop_states == {True, False}
    # nothing of the hand-placed items is a plain or an anycast prefix
    assert {p for p, _per in f.kinds.values()} <= set(f.complex_prefixes())


@pytest.mark.parametrize("kind,has_route", [
    ("weighted", True), ("partly_weighted", True), ("min_nexthop", True),
    ("min_nexthop_weighted", True), ("mixed_metrics", True),
    ("unknown_alone", False), ("unknown_among", True), ("roots_own", False),
])
def test_a_non_ksp_item_is_touched_only_by_its_own_advertisers(kind, has_route):
    """One uplink a known advertiser raised and restored, then one of a
    ToR that advertises nothing of the item."""
    f = Fabric()
    prefix, per_node = f.kinds[kind]
    assert (prefix in f.rdb.unicast_routes) == has_route
    tors = {topo.node_name(topo.fat_tree_tor(f.g, p, j)): (p, j)
            for p in range(1, 4) for j in range(2)}
    mine = sorted(n for n in per_node if n in tors)
    # ToR (1, 1) advertises none of the hand-placed items but `min_nexthop`
    other = next(n for n in sorted(tors, reverse=True) if n not in per_node)
    for name, expect in [(n, True) for n in mine] + [(other, False)]:
        pod, j = tors[name]
        link = (topo.fat_tree_agg(f.g, pod, 0), topo.fat_tree_tor(f.g, pod, j))
        for metric in (10, 1):
            touched, changed, prev = f.warm(set_link(f.adj_dbs, f.ls, *link, metric))
            assert changed == {name}
            assert (prefix in touched) == expect, (name, metric)
            if not expect:
                assert f.rdb.unicast_routes.get(prefix) is prev.unicast_routes.get(prefix)
    # the ghost's item alone has no known advertiser: no round can name it
    assert bool(mine) == (kind != "unknown_alone")


# ----------------------------------------------------------------------- (c)


def entry(prefix, **fields):
    return PrefixEntry(prefix=prefix, **fields)


def test_the_complex_table_holds_known_advertisers_and_the_ksp_items():
    p = [IpPrefix.make(f"10.9.{i}.0/24") for i in range(7)]
    entries = {
        p[0]: {"a": entry(p[0])},                                  # plain
        p[1]: {"a": entry(p[1]), "b": entry(p[1])},                # anycast
        p[2]: {"c": entry(p[2], weight=2), "a": entry(p[2])},      # partly weighted
        p[3]: {GHOST: entry(p[3])},                                # unknown alone
        p[4]: {"b": entry(p[4], forwarding_algorithm=KSP)},        # KSP
        p[5]: {"a": entry(p[5]), GHOST: entry(p[5], forwarding_algorithm=KSP),
               "c": entry(p[5], min_nexthop=2)},                   # KSP, unknown
        p[6]: {"b": entry(p[6], min_nexthop=3)},                   # constrained
    }
    ids = {"a": 4, "b": 0, "c": 9}
    view = build_elect_view(entries, ids, gen=("t", 1, 1))
    assert view.plain_p == [p[0]] and view.multi.prefixes == [p[1]]
    assert [q for q, _per in view.complex_items] == p[2:]
    t = view.complex_table
    assert t.adv.dtype == t.seg.dtype == t.whole_graph.dtype == np.int64
    # one slot a KNOWN advertiser; the ghost has none, so item 1 has none
    slots = sorted(zip(t.seg.tolist(), t.adv.tolist()))
    assert slots == [(0, 4), (0, 9), (2, 0), (3, 4), (3, 9), (4, 0)]
    assert t.whole_graph.tolist() == [2, 3]
    # the items are copies: the live per-node dicts mutate in place
    assert view.complex_items[0][1] == entries[p[2]]
    assert view.complex_items[0][1] is not entries[p[2]]


def test_a_view_with_no_complex_item_has_an_empty_table():
    p = IpPrefix.make("10.9.0.0/24")
    view = build_elect_view({p: {"a": entry(p)}}, {"a": 0}, gen=("t", 1, 1))
    t = view.complex_table
    assert view.complex_items == []
    assert (len(t.adv), len(t.seg), len(t.whole_graph)) == (0, 0, 0)
    # the scope's expression holds on it
    mask = np.ones(4, bool)
    assert np.unique(t.seg[mask[t.adv]]).tolist() == []


# ----------------------------------------------------------------------- (d)


@pytest.mark.parametrize("dirt_kind", [None, "weighted", "unknown_alone"])
def test_a_fully_reverted_window_touches_the_dirt_and_the_ksp_items_alone(dirt_kind):
    f = Fabric()
    link = (topo.fat_tree_agg(f.g, 1, 0), topo.fat_tree_tor(f.g, 1, 0))
    pairs = set_link(f.adj_dbs, f.ls, *link, 10)
    pairs += set_link(f.adj_dbs, f.ls, *link, 1)
    dirt = set()
    if dirt_kind is not None:
        # prefix dirt in the same window: the advertiser's entry changes
        prefix, per_node = f.kinds[dirt_kind]
        node = sorted(per_node)[0]
        dirt = f.ps.update_prefix_db(PrefixDatabase(
            this_node_name=node, prefix_entries=(
                dataclasses.replace(f.ps.prefixes[prefix][node], weight=7),)))
        assert dirt == {prefix}
    solves = f.solver.solve_count
    touched, changed, _prev = f.warm(pairs, dirt)
    assert f.solver.solve_count == solves and changed == set()
    assert touched == dirt | f.ksp_items
    assert f.grew["complex_scoped"] == 0 and f.grew["multi_scoped"] == 0
    assert f.grew["general_prefixes"] == len(dirt) + 2
