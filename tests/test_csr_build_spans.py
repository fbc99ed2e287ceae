"""`LinkState._build_csr`'s five spans and `decision.csr.adjacencies_walked`
at k = 4: the spans nest under `spf:to_csr` in a build and are absent from
a re-base and a patched view; the walk's count is the LSDB's adjacencies,
worked out by hand, and a drain or a metric flap leaves it unmoved."""

import dataclasses

import pytest

from openr_tpu.decision.linkstate import LinkState
from openr_tpu.monitor import names, profiling
from perfbench import topo
from perfbench.drivers.decision_fib import program_dbs

BUILD = ("spf:csr_index", "spf:csr_walk", "spf:csr_collapse", "spf:csr_sort",
         "spf:csr_masks")


def fabric() -> tuple[LinkState, list]:
    """k = 4: 4 spines, 8 aggregation switches, 8 ToRs; every ToR has 2
    uplinks, every agg 2 downlinks and 2 uplinks, every spine 4 downlinks:
    8·2 + 8·4 + 4·4 = 64 adjacencies."""
    dbs, _ = program_dbs(topo.fat_tree(4))
    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_db(db)
    return ls, dbs


def spans_of(ls: LinkState) -> list[tuple[str, str]]:
    """(name, parent) of every span `to_csr` opened, as the solver calls it."""
    with profiling.collect() as rec:
        with profiling.annotate("spf:to_csr"):
            ls.to_csr()
    return [(name, parent) for name, parent, _s, _e in rec.spans]


def test_the_five_spans_are_registered_under_to_csr():
    order = list(names.REBUILD_SPANS)
    at = order.index("spf:to_csr")
    assert tuple(order[at + 1:at + 6]) == BUILD
    assert "decision.csr.adjacencies_walked" in names.COUNTERS


def test_a_build_opens_the_five_spans_in_order_under_to_csr():
    ls, _dbs = fabric()
    spans = spans_of(ls)
    assert spans == [*((name, "spf:to_csr") for name in BUILD),
                     ("spf:to_csr", None)]
    assert ls.csr_stats == {"builds": 1, "overload_rebases": 0,
                            "adjacencies_walked": 64,
                            "databases_converted": 20,
                            "interning_kept": 0}


def test_the_walk_counts_what_it_reads_a_one_sided_adjacency_included():
    """One ToR withdraws one uplink; the agg still advertises it. The
    bidirectional check drops the link both ways, and the walk read 63
    adjacencies: every one the LSDB holds, the agg's of that link
    included."""
    ls, dbs = fabric()
    ls.to_csr()
    g = topo.fat_tree(4)
    tor = topo.fat_tree_tor(g, 1, 0)
    db = dbs[tor]
    ls.update_adjacency_db(dataclasses.replace(
        db, adjacencies=db.adjacencies[1:]))
    csr = ls.to_csr()
    assert ls.csr_stats["builds"] == 2
    assert ls.csr_stats["adjacencies_walked"] == 64 + 63
    assert csr.num_edges == 62  # both directions of the link are gone


@pytest.mark.parametrize("change", ["drain", "metric"])
def test_a_re_base_or_a_patch_opens_no_build_span_and_walks_nothing(change):
    ls, dbs = fabric()
    ls.to_csr()
    walked = dict(ls.csr_stats)
    node = topo.fat_tree_agg(topo.fat_tree(4), 2, 1)
    db = dbs[node]
    if change == "drain":
        db = dataclasses.replace(db, is_overloaded=True)
    else:
        first = dataclasses.replace(db.adjacencies[0], metric=10)
        db = dataclasses.replace(db, adjacencies=(first, *db.adjacencies[1:]))
    ls.update_adjacency_db(db)
    spans = spans_of(ls)
    assert spans == [("spf:to_csr", None)]
    grew = {k: ls.csr_stats[k] - walked[k] for k in walked}
    assert grew == {"builds": 0, "overload_rebases": int(change == "drain"),
                    "adjacencies_walked": 0, "databases_converted": 0,
                    "interning_kept": 0}
