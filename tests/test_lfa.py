"""LFA (RFC 5286 loop-free alternate) tests — BASELINE config 4's
backup-path component. TPU solver and oracle must agree exactly; known
topologies pin the semantics."""

import numpy as np
import pytest

from openr_tpu.decision.linkstate import LinkState, PrefixState
from openr_tpu.decision.oracle import compute_routes as oracle_routes
from openr_tpu.decision.spf_backend import TpuSpfSolver
from openr_tpu.types.topology import (
    Adjacency,
    AdjacencyDatabase,
    PrefixDatabase,
    PrefixEntry,
)
from openr_tpu.types.network import IpPrefix
from openr_tpu.utils import topogen


def adj(other, ifn, metric):
    return Adjacency(
        other_node_name=other, if_name=ifn, other_if_name=f"r-{ifn}",
        metric=metric,
    )


def db(node, *adjs, overloaded=False):
    return AdjacencyDatabase(
        this_node_name=node, adjacencies=tuple(adjs),
        is_overloaded=overloaded,
    )


def states(adj_dbs, prefix_map):
    ls, ps = LinkState(), PrefixState()
    for d in adj_dbs:
        ls.update_adjacency_db(d)
    for node, pfx in prefix_map.items():
        ps.update_prefix_db(
            PrefixDatabase(
                this_node_name=node,
                prefix_entries=(PrefixEntry(prefix=IpPrefix.make(pfx)),),
            )
        )
    return ls, ps


def test_lfa_square_topology():
    """S—A—D (cost 1+1) and S—B—D (cost 1+2): primary to D via A; B is a
    loop-free alternate because dist_B(D)=2 (direct) is strictly less
    than dist_B(S)+dist_S(D)=1+2."""
    dbs = [
        db("s", adj("a", "sa", 1), adj("b", "sb", 1)),
        db("a", adj("s", "as", 1), adj("d", "ad", 1)),
        db("b", adj("s", "bs", 1), adj("d", "bd", 2)),
        db("d", adj("a", "da", 1), adj("b", "db", 2)),
    ]
    ls, ps = states(dbs, {"d": "10.0.0.4/32"})
    rib = TpuSpfSolver(enable_lfa=True).compute_routes(ls, ps, "s")
    entry = rib.unicast_routes[IpPrefix.make("10.0.0.4/32")]
    assert [nh.address for nh in entry.nexthops] == ["a"]
    assert [nh.address for nh in entry.backup_nexthops] == ["b"]
    # backup metric = metric(s→b) + dist_b(d) = 1 + 2
    assert entry.backup_nexthops[0].metric == 3


def test_lfa_excluded_when_looping():
    """Line b—s—a—d: b's only path to d goes back through s, so b is NOT
    a loop-free alternate."""
    dbs = [
        db("s", adj("a", "sa", 1), adj("b", "sb", 1)),
        db("a", adj("s", "as", 1), adj("d", "ad", 1)),
        db("b", adj("s", "bs", 1)),
        db("d", adj("a", "da", 1)),
    ]
    ls, ps = states(dbs, {"d": "10.0.0.4/32"})
    rib = TpuSpfSolver(enable_lfa=True).compute_routes(ls, ps, "s")
    entry = rib.unicast_routes[IpPrefix.make("10.0.0.4/32")]
    assert entry.backup_nexthops == ()


def test_lfa_overloaded_neighbor_excluded():
    """An overloaded neighbor can't be an LFA (no transit) unless it IS
    the destination."""
    dbs = [
        db("s", adj("a", "sa", 1), adj("b", "sb", 1)),
        db("a", adj("s", "as", 1), adj("d", "ad", 1)),
        db("b", adj("s", "bs", 1), adj("d", "bd", 2), overloaded=True),
        db("d", adj("a", "da", 1), adj("b", "db", 2)),
    ]
    ls, ps = states(dbs, {"d": "10.0.0.4/32", "b": "10.0.0.2/32"})
    rib = TpuSpfSolver(enable_lfa=True).compute_routes(ls, ps, "s")
    d_entry = rib.unicast_routes[IpPrefix.make("10.0.0.4/32")]
    assert d_entry.backup_nexthops == ()  # b overloaded → not an LFA for d


@pytest.mark.parametrize("topo", ["grid", "ring", "fat_tree"])
def test_lfa_tpu_matches_oracle(topo):
    if topo == "grid":
        adj_dbs, prefix_dbs = topogen.grid(4, 4)
    elif topo == "ring":
        adj_dbs, prefix_dbs = topogen.ring(8)
    else:
        adj_dbs, prefix_dbs = topogen.fat_tree(4)
    ls, ps = LinkState(), PrefixState()
    for d in adj_dbs:
        ls.update_adjacency_db(d)
    for pdb in prefix_dbs:
        ps.update_prefix_db(pdb)
    for me in [d.this_node_name for d in adj_dbs][:6]:
        tpu = TpuSpfSolver(enable_lfa=True).compute_routes(ls, ps, me)
        ora = oracle_routes(ls, ps, me, enable_lfa=True)
        assert tpu.unicast_routes == ora.unicast_routes, me


def test_lfa_weighted_random_matches_oracle_with_backups():
    """Weighted random graphs (asymmetric costs break the equal-cost
    degeneracy of uniform topologies, so strict LFAs exist): TPU ==
    oracle everywhere, and backups actually occur."""
    rng = np.random.default_rng(11)
    n = 24
    names = [f"w{i}" for i in range(n)]
    edges = {}
    # connected ring + random chords, independent per-direction metrics
    for i in range(n):
        edges[(i, (i + 1) % n)] = int(rng.integers(1, 20))
        edges[((i + 1) % n, i)] = int(rng.integers(1, 20))
    for _ in range(2 * n):
        a, b = rng.integers(0, n, 2)
        if a != b:
            edges[(int(a), int(b))] = int(rng.integers(1, 20))
            edges[(int(b), int(a))] = int(rng.integers(1, 20))
    by_src = {}
    for (a, b), m in edges.items():
        by_src.setdefault(a, []).append((b, m))
    dbs = [
        db(
            names[a],
            *[adj(names[b], f"if{a}-{b}", m) for b, m in sorted(outs)],
        )
        for a, outs in sorted(by_src.items())
    ]
    ls, ps = states(
        dbs, {names[i]: f"10.1.{i}.0/24" for i in range(n)}
    )
    total_backups = 0
    for me in names[:8]:
        tpu = TpuSpfSolver(enable_lfa=True).compute_routes(ls, ps, me)
        ora = oracle_routes(ls, ps, me, enable_lfa=True)
        assert tpu.unicast_routes == ora.unicast_routes, me
        total_backups += sum(
            len(e.backup_nexthops) for e in tpu.unicast_routes.values()
        )
    assert total_backups > 0


def _nh(node, ifn, metric, area):
    from openr_tpu.types.network import NextHop

    return NextHop(address=node, if_name=ifn, metric=metric,
                   neighbor_node=node, area=area)


def _two_area_case(igp2=10):
    """One prefix in areas "1" and "2": area "1" reaches it through a at
    10 with backups b (30) and c (40); area "2" through c at `igp2`, with
    backups b (25), d (50) and e (60, the same metric as area "1"'s e)."""
    from openr_tpu.types.routes import RibEntry, RouteDatabase

    p = IpPrefix.make("10.9.0.1/32")
    e1 = RibEntry(
        prefix=p, nexthops=(_nh("a", "ia", 10, "1"),), best_node="x",
        igp_cost=10, backup_nexthops=(
            _nh("b", "ib", 30, "1"), _nh("c", "ic", 40, "1"),
            _nh("e", "ie", 60, "1")),
    )
    e2 = RibEntry(
        prefix=p, nexthops=(_nh("c", "ic", igp2, "2"),), best_node="x",
        igp_cost=igp2, backup_nexthops=(
            _nh("b", "ib", 25, "2"), _nh("d", "id", 50, "2"),
            _nh("e", "ie", 60, "2")),
    )
    per_area = {}
    for area, e in (("1", e1), ("2", e2)):
        rdb = RouteDatabase(this_node_name="s")
        rdb.unicast_routes[p] = e
        per_area[area] = rdb
    return p, e1, e2, per_area


def test_fold_at_a_tie_unions_backups_less_the_merged_primaries():
    """docs/Decision.md "LFA backups across areas": at equal cost the
    primaries are the union; the backups are both areas' less any slot
    that is a merged primary, a repeat at its least metric, the earlier
    area's at equal metric."""
    from openr_tpu.decision.decision import (
        _fold_unicast,
        merge_area_ribs,
        merge_scope_delta,
    )
    from openr_tpu.types.routes import RouteDatabase

    p, e1, e2, per_area = _two_area_case()
    merged = _fold_unicast(e1, e2)
    assert [(nh.neighbor_node, nh.area) for nh in merged.nexthops] == [
        ("a", "1"), ("c", "2")]
    assert [(nh.neighbor_node, nh.metric, nh.area)
            for nh in merged.backup_nexthops] == [
        ("b", 25, "2"), ("d", 50, "2"), ("e", 60, "1")]
    # the full fold and the scoped one agree
    full = merge_area_ribs(per_area, "s").unicast_routes[p]
    scoped = merge_scope_delta(
        per_area, RouteDatabase(this_node_name="s"), {p}
    ).unicast_to_update[p]
    assert full == scoped == merged


def test_fold_off_the_tie_keeps_the_winner_s_backups():
    from openr_tpu.decision.decision import _fold_unicast

    _p, e1, e2, _ = _two_area_case(igp2=5)
    assert _fold_unicast(e1, e2) is e2
    _p, e1, e2, _ = _two_area_case(igp2=15)
    assert _fold_unicast(e1, e2) is e1
    # no backups on either side: the merged route has none
    import dataclasses

    a = dataclasses.replace(e1, backup_nexthops=())
    b = dataclasses.replace(_two_area_case()[2], backup_nexthops=())
    assert _fold_unicast(a, b).backup_nexthops == ()


def test_lfa_on_anycast_prefixes_matches_oracle():
    """A prefix two routers advertise (several chosen targets): the
    backups the one pass builds for it equal the oracle's, and some
    anycast route has one."""
    rng = np.random.default_rng(5)
    n = 16
    names = [f"a{i}" for i in range(n)]
    by_src: dict = {}
    for i in range(n):
        for j in ((i + 1) % n, (i + 5) % n):
            m = int(rng.integers(1, 9))
            by_src.setdefault(i, {})[j] = m
            by_src.setdefault(j, {})[i] = m
    dbs = [
        db(names[a], *[adj(names[b], f"if{a}-{b}", m)
                       for b, m in sorted(outs.items())])
        for a, outs in sorted(by_src.items())
    ]
    ls, ps = LinkState(), PrefixState()
    for d in dbs:
        ls.update_adjacency_db(d)
    for i in range(n):
        entries = [PrefixEntry(prefix=IpPrefix.make(f"10.2.{i}.0/24"))]
        if i in (3, 11):
            entries.append(PrefixEntry(prefix=IpPrefix.make("10.9.9.0/24")))
        ps.update_prefix_db(PrefixDatabase(
            this_node_name=names[i], prefix_entries=tuple(entries)))
    anycast = IpPrefix.make("10.9.9.0/24")
    backed = 0
    for me in names[:8]:
        tpu = TpuSpfSolver(enable_lfa=True).compute_routes(ls, ps, me)
        ora = oracle_routes(ls, ps, me, enable_lfa=True)
        assert tpu.unicast_routes == ora.unicast_routes, me
        e = tpu.unicast_routes.get(anycast)
        backed += bool(e is not None and e.backup_nexthops)
    assert backed > 0
