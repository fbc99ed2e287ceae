"""`LinkState._build_csr` against a plain row-by-row builder.

`row_by_row` below is the CSR build as a walk over every adjacency in name
order with Python sets and dicts, kept here independent of the program's
columnar build. On seeded random LSDBs, one case a shape the build must
get right, the two agree on every array byte for byte, on `edge_index` and
`adj_details` as mappings and on `details()` of every edge, build after
build of one LinkState (so the columns kept between builds are exercised).
"""

import dataclasses
import random

import numpy as np
import pytest

from openr_tpu.common.constants import METRIC_MAX
from openr_tpu.common.util import pad_bucket
from openr_tpu.decision.linkstate import (
    INF_METRIC,
    EdgeDetails,
    EdgeIndex,
    LinkState,
)
from openr_tpu.types.topology import Adjacency, AdjacencyDatabase


def row_by_row(adj_dbs: dict[str, AdjacencyDatabase]) -> dict:
    """The CSR of `adj_dbs`: an edge u->w for every adjacency whose
    neighbor has a database and lists u back, unless the adjacency or its
    far end (the neighbor's adjacency on `other_if_name`) is overloaded;
    parallel edges collapsed to their least metric, clamped to METRIC_MAX;
    edges sorted by (dst, src)."""
    names = sorted(adj_dbs)
    ids = {n: i for i, n in enumerate(names)}
    has_reverse, drained = set(), set()
    for node, db in adj_dbs.items():
        for adj in db.adjacencies:
            has_reverse.add((node, adj.other_node_name))
            if adj.is_overloaded:
                drained.add((node, adj.if_name))
    details: dict[tuple[int, int], list] = {}
    best: dict[tuple[int, int], int] = {}
    for node in names:
        for adj in adj_dbs[node].adjacencies:
            if adj.other_node_name not in ids:
                continue
            if (adj.other_node_name, node) not in has_reverse:
                continue
            if adj.is_overloaded or (
                    adj.other_node_name, adj.other_if_name) in drained:
                continue
            key = (ids[node], ids[adj.other_node_name])
            details.setdefault(key, []).append((
                adj.if_name, int(adj.metric), int(adj.weight),
                int(adj.adj_label), adj.other_if_name))
            best[key] = min(best.get(key, int(adj.metric)), int(adj.metric))
    v, e = len(names), len(best)
    vp = pad_bucket(max(v, 1) + 1)
    ep = pad_bucket(max(e, 1), minimum=128)
    src = np.zeros(ep, dtype=np.int32)
    dst = np.full(ep, vp - 1, dtype=np.int32)
    metric = np.full(ep, INF_METRIC, dtype=np.int32)
    index = {}
    for i, ((s, d), m) in enumerate(
            sorted(best.items(), key=lambda kv: (kv[0][1], kv[0][0]))):
        src[i], dst[i], metric[i] = s, d, min(m, METRIC_MAX)
        index[(s, d)] = i
    mask = np.zeros(vp, dtype=bool)
    over = np.zeros(vp, dtype=bool)
    for n, i in ids.items():
        mask[i] = True
        over[i] = adj_dbs[n].is_overloaded
    return {"num_nodes": v, "num_edges": e, "edge_src": src, "edge_dst": dst,
            "edge_metric": metric, "node_mask": mask, "node_overloaded": over,
            "edge_index": index, "adj_details": details, "node_names": names}


def assert_same(csr, want: dict) -> None:
    for name in ("edge_src", "edge_dst", "edge_metric", "node_mask",
                 "node_overloaded"):
        got = getattr(csr, name)
        assert got.dtype == want[name].dtype, name
        assert got.tobytes() == want[name].tobytes(), name
    assert csr.num_nodes == want["num_nodes"]
    assert csr.num_edges == want["num_edges"]
    assert csr.node_names == want["node_names"]
    assert isinstance(csr.edge_index, EdgeIndex)
    assert isinstance(csr.adj_details, EdgeDetails)
    assert list(csr.edge_index.items()) == list(want["edge_index"].items())
    assert dict(csr.adj_details) == want["adj_details"]
    for key, lst in want["adj_details"].items():
        assert csr.details(*key) == lst
        assert csr.details_get(*key) == lst


# ------------------------------------------------------------ the LSDBs


def link(dbs: dict, u: str, v: str, k: int, m_uv: int, m_vu: int,
         over_u=False, over_v=False, one_sided=False) -> None:
    """A link between u and v on interfaces `u-k` / `v-k`, an adjacency
    at each end (only at u's where `one_sided`)."""
    dbs[u].append(Adjacency(other_node_name=v, if_name=f"{u}-{k}",
                            metric=m_uv, is_overloaded=over_u,
                            adj_label=100 + k, weight=1 + k % 3,
                            other_if_name=f"{v}-{k}"))
    if not one_sided:
        dbs[v].append(Adjacency(other_node_name=u, if_name=f"{v}-{k}",
                                metric=m_vu, is_overloaded=over_v,
                                adj_label=200 + k, weight=1 + k % 2,
                                other_if_name=f"{u}-{k}"))


def random_lsdb(rng: random.Random, case: str, n: int = 24) -> dict:
    """name -> AdjacencyDatabase: a random graph with the case's shape
    planted in it, several times over."""
    names = [f"node-{rng.randrange(10**6):06d}-{i}" for i in range(n)]
    dbs: dict[str, list] = {name: [] for name in names}
    k = 0
    for _ in range(3 * n):
        u, v = rng.sample(names, 2)
        k += 1
        link(dbs, u, v, k, rng.randint(1, 50), rng.randint(1, 50))
    for _ in range(4):
        u, v = rng.sample(names, 2)
        if case == "parallel_links_at_different_metrics":
            for m in rng.sample(range(1, 60), 3):
                k += 1
                link(dbs, u, v, k, m, m + rng.randint(0, 3))
        elif case == "one_sided_adjacency":
            k += 1
            link(dbs, u, v, k, 5, 5, one_sided=True)
        elif case == "adjacency_to_a_node_with_no_database":
            k += 1
            dbs[u].append(Adjacency(other_node_name=f"ghost-{k}",
                                    if_name=f"{u}-{k}", metric=3,
                                    other_if_name=f"ghost-{k}-0"))
        elif case == "overloaded_adjacency_with_a_parallel_link_up":
            k += 1
            link(dbs, u, v, k, 7, 7, over_u=rng.random() < 0.5,
                 over_v=True)
            k += 1
            link(dbs, u, v, k, 9, 9)
        elif case == "metric_above_metric_max":
            k += 1
            link(dbs, u, v, k, METRIC_MAX + rng.randint(1, 10**6),
                 METRIC_MAX * 3)
    for db in dbs.values():
        rng.shuffle(db)
    over = set()
    if case == "overloaded_nodes":
        over = set(rng.sample(names, 4))
    return {name: AdjacencyDatabase(this_node_name=name,
                                    adjacencies=tuple(adjs),
                                    is_overloaded=name in over)
            for name, adjs in dbs.items()}


def next_lsdb(rng: random.Random, case: str, dbs: dict) -> dict:
    """The LSDB after one change of the case's kind, the unchanged
    databases the same objects."""
    dbs = dict(dbs)
    names = sorted(dbs)
    if case == "node_added_and_one_removed":
        gone = rng.choice(names)
        del dbs[gone]
        new = f"node-new-{rng.randrange(10**6)}"
        peers = rng.sample(sorted(dbs), 3)
        adjs = {new: [], **{p: list(dbs[p].adjacencies) for p in peers}}
        for i, p in enumerate(peers):
            link(adjs, new, p, 900 + i, 4, 4)
        for name, lst in adjs.items():
            dbs[name] = AdjacencyDatabase(this_node_name=name,
                                          adjacencies=tuple(lst))
    else:  # a database changed: one adjacency dropped, one metric moved
        node = rng.choice([n for n in names if len(dbs[n].adjacencies) > 1])
        adjs = list(dbs[node].adjacencies)
        adjs.pop(rng.randrange(len(adjs)))
        adjs[0] = dataclasses.replace(adjs[0], metric=adjs[0].metric + 1)
        dbs[node] = dataclasses.replace(dbs[node], adjacencies=tuple(adjs))
    return dbs


CASES = [
    "parallel_links_at_different_metrics",
    "one_sided_adjacency",
    "adjacency_to_a_node_with_no_database",
    "overloaded_adjacency_with_a_parallel_link_up",
    "overloaded_nodes",
    "metric_above_metric_max",
    "node_added_and_one_removed",
    "database_changed_others_reused",
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("seed", [1, 2147483659])
def test_the_columnar_build_is_the_row_by_row_build(case, seed):
    rng = random.Random(f"{case}/{seed}")
    dbs = random_lsdb(rng, case)
    ls = LinkState()
    for db in dbs.values():
        ls.update_adjacency_db(db)
    assert_same(ls.to_csr(), row_by_row(dbs))
    assert ls.csr_stats["databases_converted"] == len(dbs)
    for _ in range(3):
        new = next_lsdb(rng, case, dbs)
        changed = [n for n in new if dbs.get(n) is not new[n]]
        for n in set(dbs) - set(new):
            ls.delete_adjacency_db(n)
        for n in changed:
            ls.update_adjacency_db(new[n])
        before = ls.csr_stats["databases_converted"]
        csr = ls.to_csr()
        assert_same(csr, row_by_row(new))
        converted = ls.csr_stats["databases_converted"] - before
        # a new node set re-interns every id; else the changed alone
        assert converted == (len(new) if set(new) != set(dbs)
                             else len(changed))
        assert ls.csr_stats["adjacencies_walked"] > 0
        dbs = new


def test_the_maps_answer_as_dicts_for_keys_that_are_no_edge():
    rng = random.Random(7)
    dbs = random_lsdb(rng, "one_sided_adjacency")
    ls = LinkState()
    for db in dbs.values():
        ls.update_adjacency_db(db)
    csr = ls.to_csr()
    want = row_by_row(dbs)
    v = csr.num_nodes
    probes = [(u, w) for u in range(-1, v + 1) for w in range(-1, v + 1)]
    probes += [(None, 0), (0, None), "ab", (0, 1, 2)]
    for key in probes:
        hit = key in want["edge_index"] if isinstance(key, tuple) else False
        assert (key in csr.edge_index) is hit, key
        assert (key in csr.adj_details) is hit, key
        assert csr.edge_index.get(key, -7) == (
            want["edge_index"][key] if hit else -7)
        assert csr.adj_details.get(key) == (
            want["adj_details"][key] if hit else None)
        if not hit:
            with pytest.raises(KeyError):
                csr.edge_index[key]
            with pytest.raises(KeyError):
                csr.adj_details[key]
    # numpy ids answer as Python ints do
    s, d = next(iter(want["edge_index"]))
    slot = want["edge_index"][(s, d)]
    assert csr.edge_index[(np.int32(s), np.int32(d))] == slot
    assert len(csr.edge_index) == len(csr.adj_details) == csr.num_edges
    # a read is kept: the same list object a second time
    assert csr.adj_details[(s, d)] is csr.details(s, d)


def test_an_empty_lsdb_and_one_node_alone_build():
    ls = LinkState()
    ls.update_adjacency_db(AdjacencyDatabase(this_node_name="solo"))
    csr = ls.to_csr()
    assert_same(csr, row_by_row({"solo": ls.adjacency_db("solo")}))
    assert list(csr.edge_index) == [] and dict(csr.adj_details) == {}
    ls.delete_adjacency_db("solo")
    assert_same(ls.to_csr(), row_by_row({}))


def test_builds_on_snapshots_of_other_node_sets_race_safely():
    """The columns are shared with snapshots. Builds of snapshots taken
    at different LSDBs, some with other node sets, run at once on eight
    threads with a short switch interval; each agrees with the row-by-row
    build of its own LSDB every time."""
    import sys
    import threading

    rng = random.Random(13)
    states = [random_lsdb(rng, "overloaded_adjacency_with_a_parallel_link_up",
                          n=16)]
    for i in range(5):
        states.append(next_lsdb(
            rng, "node_added_and_one_removed" if i % 2 else "changed",
            states[-1]))
    ls = LinkState()
    snaps = []
    for state in states:
        for n in set(ls.nodes) - set(state):
            ls.delete_adjacency_db(n)
        for db in state.values():
            ls.update_adjacency_db(db)
        snaps.append(ls.snapshot())
    wants = [row_by_row(state) for state in states]
    failures: list = []

    def work(k: int) -> None:
        try:
            for j in range(12):
                i = (k + j) % len(snaps)
                assert_same(snaps[i]._build_csr(), wants[i])
        except Exception as exc:  # reported by the main thread
            failures.append(repr(exc))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert failures == []
