"""The node interning of a built CSR (`CsrGraph.interning_key`), on k = 4
and k = 8 fabrics.

`LinkState._build_csr` interns node names in sorted order, so a build of
the same node set gives every node the id it had. Such a build keeps the
last build's `node_names` and `name_to_id` (the same objects) and its
`interning_version` (`decision.csr.interning_kept`), while its
`structure_key` is its own. The solver keys what reads ids and names alone
on the interning (the election view, through its `gen` the unicast route
cell, the MPLS class groups), and what reads edges or the databases'
contents on the structure (the neighbour list, the node-label vector).

- a link down and up keeps the key and the very maps, and the assembly
  keeps its unicast cell (`decision.spf.assembly_carried`): every route
  the link does not move is the last RIB's object;
- a node removed, added or renamed interns anew: a new key and new
  views;
- an overload re-base keeps the key, and so does a build after it;
- a node label changed alone or with an adjacency change gives the MPLS
  routes of a fresh solver and of the oracle;
- the root's own link going down changes the neighbour list and the
  routes, again those of a fresh solver.
"""

import dataclasses

import pytest

from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.oracle import compute_routes as oracle_compute_routes
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.monitor import names
from perfbench import topo
from perfbench.drivers.decision_fib import program_dbs

KS = (4, 8)


class Fabric:
    """A k-ary fat tree's databases in one live LinkState and PrefixState,
    the root the first ToR of pod 0, and a solver that has its first RIB."""

    def __init__(self, k: int):
        self.g = topo.fat_tree(k)
        adj_dbs, self.prefix_dbs = program_dbs(self.g)
        self.whole = {db.this_node_name: db for db in adj_dbs}
        self.dbs = dict(self.whole)
        self.ls = LinkState()
        for db in adj_dbs:
            self.ls.update_adjacency_db(db)
        self.ps = prefixes(self.prefix_dbs)
        self.me = topo.node_name(topo.fat_tree_tor(self.g, 0, 0))
        self.solver = device_solver()
        self.rib = self.routes()

    def routes(self):
        return self.solver.compute_routes(self.ls, self.ps, self.me)

    def apply(self, db) -> None:
        self.dbs[db.this_node_name] = db
        assert self.ls.update_adjacency_db(db)

    def link(self, u: int, v: int, up: bool) -> None:
        """The link u<->v withdrawn at both ends, or put back (the two
        databases as the fabric first had them)."""
        a, b = topo.node_name(u), topo.node_name(v)
        for me, peer in ((a, b), (b, a)):
            db = self.dbs[me]
            self.apply(self.whole[me] if up else dataclasses.replace(
                db, adjacencies=tuple(
                    x for x in db.adjacencies if x.other_node_name != peer)))

    @property
    def carried(self) -> int:
        return self.solver.spf_kernel_stats["assembly_carried"]


def prefixes(prefix_dbs) -> PrefixState:
    ps = PrefixState()
    for pdb in prefix_dbs:
        ps.update_prefix_db(pdb)
    return ps


def device_solver() -> TpuSpfSolver:
    return TpuSpfSolver(native_rib="off")


def assert_fresh(f: Fabric, got) -> None:
    """`got` is what a fresh solver gives over a fresh copy of the LSDB,
    and what the scalar oracle gives."""
    ls = LinkState()
    for db in f.dbs.values():
        ls.update_adjacency_db(db)
    want = device_solver().compute_routes(ls, prefixes(f.prefix_dbs), f.me)
    oracle = oracle_compute_routes(f.ls, f.ps, f.me, vectorize=False)
    for other in (want, oracle):
        assert got.unicast_routes == other.unicast_routes
        assert got.mpls_routes == other.mpls_routes


def other_pod_uplink(f: Fabric) -> tuple[int, int]:
    return topo.fat_tree_tor(f.g, 1, 0), topo.fat_tree_agg(f.g, 1, 1)


def test_the_counter_is_registered():
    assert names.is_registered("decision.csr.interning_kept")


@pytest.mark.parametrize("k", KS)
def test_a_link_down_and_up_keeps_the_interning_and_the_maps(k):
    f = Fabric(k)
    first = f.ls.to_csr()
    assert first.interning_key == first.structure_key  # the first build's
    view = f.ps.election_view(first.name_to_id, first.interning_key)
    tor, agg = other_pod_uplink(f)
    loopback = f.prefix_dbs[tor].prefix_entries[0].prefix
    label = topo.node_label(tor)
    before = f.rib
    for kept, up in ((1, False), (2, True)):
        f.link(tor, agg, up)
        csr = f.ls.to_csr()
        assert f.ls.csr_stats["builds"] == 1 + kept
        assert f.ls.csr_stats["interning_kept"] == kept
        assert csr.structure_key != first.structure_key
        assert csr.interning_key == first.interning_key
        assert csr.name_to_id is first.name_to_id
        assert csr.node_names is first.node_names
        assert f.ps.election_view(csr.name_to_id, csr.interning_key) is view
        got = f.routes()
        assert f.carried == kept
        assert_fresh(f, got)
        # the link moves the ToR's loopback and label alone; every other
        # route is the last RIB's object, which a diff passes by identity
        was_u, was_m = before.unicast_routes, before.mpls_routes
        assert {p for p, e in got.unicast_routes.items()
                if e != was_u[p]} == {loopback}
        assert {p for p, e in got.mpls_routes.items()
                if e != was_m[p]} == {label}
        assert all(e is was_u[p] for p, e in got.unicast_routes.items()
                   if p != loopback)
        assert all(e is was_m[p] for p, e in got.mpls_routes.items()
                   if p != label)
        before = got
    assert got.unicast_routes == f.rib.unicast_routes


def rename(f: Fabric, old: str, new: str) -> None:
    """Node `old` gone and node `new` up with its links, in one build."""
    del f.dbs[old]
    assert f.ls.delete_adjacency_db(old)
    db = f.whole[old]
    f.apply(dataclasses.replace(db, this_node_name=new))
    for peer in {a.other_node_name for a in db.adjacencies}:
        pdb = f.dbs[peer]
        f.apply(dataclasses.replace(pdb, adjacencies=tuple(
            dataclasses.replace(a, other_node_name=new)
            if a.other_node_name == old else a
            for a in pdb.adjacencies)))


@pytest.mark.parametrize("k", KS)
def test_a_node_removed_added_or_renamed_interns_anew(k):
    f = Fabric(k)
    tor = topo.node_name(topo.fat_tree_tor(f.g, 1, 1))
    keys = [f.ls.to_csr().interning_key]
    views = [f.ps.election_view(f.ls.to_csr().name_to_id, keys[0])]
    maps = [f.ls.to_csr().name_to_id]
    for change in ("removed", "added", "renamed"):
        if change == "removed":
            del f.dbs[tor]
            assert f.ls.delete_adjacency_db(tor)
        elif change == "added":
            f.apply(f.whole[tor])
        else:  # as many nodes as before, and other ids
            rename(f, tor, "node-a")
        csr = f.ls.to_csr()
        assert csr.interning_key not in keys, change
        assert all(csr.name_to_id is not m for m in maps), change
        view = f.ps.election_view(csr.name_to_id, csr.interning_key)
        assert all(view is not v and view.gen != v.gen for v in views)
        keys.append(csr.interning_key)
        views.append(view)
        maps.append(csr.name_to_id)
        assert f.ls.csr_stats["interning_kept"] == 0
        assert_fresh(f, f.routes())
        assert f.carried == 0, change
    # the node's neighbours still list it while it is gone: -1 in their
    # columns, so no edge; back, it has every id of the first interning;
    # renamed, the node count stands and the ids after it move
    assert maps[2] == maps[0] and len(maps[1]) == len(maps[0]) - 1
    assert len(maps[3]) == len(maps[0]) and maps[3] != maps[0]


@pytest.mark.parametrize("k", KS)
def test_an_overload_rebase_keeps_the_key_and_so_does_a_build_after(k):
    f = Fabric(k)
    first = f.ls.to_csr()
    agg = topo.node_name(topo.fat_tree_agg(f.g, 1, 0))
    f.apply(dataclasses.replace(f.dbs[agg], is_overloaded=True))
    csr = f.ls.to_csr()
    assert f.ls.csr_stats["overload_rebases"] == 1
    assert csr.base_version != first.base_version
    assert csr.structure_key == first.structure_key
    assert csr.interning_key == first.interning_key
    assert csr.name_to_id is first.name_to_id
    assert_fresh(f, f.routes())
    assert f.carried == 1
    f.link(*other_pod_uplink(f), up=False)
    built = f.ls.to_csr()
    assert f.ls.csr_stats["builds"] == 2
    assert f.ls.csr_stats["interning_kept"] == 1
    assert built.structure_key not in (first.structure_key, 0)
    assert built.interning_key == first.interning_key
    assert built.node_overloaded[built.name_to_id[agg]]
    assert_fresh(f, f.routes())
    assert f.carried == 2


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("with_link", [False, True], ids=["alone", "with_link"])
def test_a_label_changed_in_a_build_that_keeps_the_ids(k, with_link):
    """A node label is no id: the label vector keys on the structure,
    which every build moves, so the new label routes and the old does
    not, whether or not an adjacency changed beside it."""
    f = Fabric(k)
    first = f.ls.to_csr()
    tor, agg = other_pod_uplink(f)
    name = topo.node_name(tor)
    old = f.dbs[name].node_label
    new = max(db.node_label for db in f.dbs.values()) + 1
    assert old in f.rib.mpls_routes
    if with_link:  # one publication: the label and the link together
        f.link(tor, agg, up=False)
    f.apply(dataclasses.replace(f.dbs[name], node_label=new))
    csr = f.ls.to_csr()
    assert f.ls.csr_stats["builds"] == 2
    assert csr.interning_key == first.interning_key
    got = f.routes()
    assert new in got.mpls_routes and old not in got.mpls_routes
    assert_fresh(f, got)


@pytest.mark.parametrize("k", KS)
def test_the_roots_own_link_down_moves_the_neighbours_and_the_routes(k):
    f = Fabric(k)
    first = f.ls.to_csr()
    my = first.name_to_id[f.me]
    root, agg = topo.fat_tree_tor(f.g, 0, 0), topo.fat_tree_agg(f.g, 0, 0)
    nbr = f.solver._structure_cell(first)["nbr"][my]
    assert first.name_to_id[topo.node_name(agg)] in nbr
    f.link(root, agg, up=False)
    csr = f.ls.to_csr()
    assert csr.interning_key == first.interning_key
    got = f.routes()
    assert f.solver._structure_cell(csr)["nbr"][my] == [
        i for i in nbr if i != first.name_to_id[topo.node_name(agg)]]
    assert got.unicast_routes != f.rib.unicast_routes
    assert_fresh(f, got)
    f.link(root, agg, up=True)
    back = f.routes()
    assert back.unicast_routes == f.rib.unicast_routes
    assert back.mpls_routes == f.rib.mpls_routes
    assert_fresh(f, back)
