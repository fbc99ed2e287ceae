"""`PrefixState.snapshot()` shared by revision (PR 29).

The per-prefix dicts are values: the writers rebind `_entries[prefix]`
and never write a dict in place, so a snapshot is one copy of the outer
dict, and while `rev` stands the same frozen object serves every
rebuild. What has to hold:

  (a) isolation: a snapshot taken before a mutation still equals a deep
      copy taken at the same instant, and the live object shows the
      mutation;
  (b) sharing: no mutation, same object; a mutation, a new one at the
      new `rev`; a publication that changes nothing keeps the memo;
  (c) the `election_view` built on a snapshot serves the live object and
      the next snapshot (`_view_cell`);
  (d) through `Decision`: the counters `decision.snapshot.prefix_shared`
      / `.prefix_copied` say which happened, and a prefix publication
      applied while a rebuild's worker thread is held shows in the next
      rebuild's RIB, not in that one's.
"""

import asyncio
import dataclasses
import threading

import pytest

from openr_tpu.common import constants as C
from openr_tpu.decision.linkstate import PrefixState
from openr_tpu.types.network import IpPrefix
from openr_tpu.types.topology import PrefixDatabase, PrefixEntry, PrefixMetrics
from tests.test_lsdb_normal_path import adj_key_of, grid_decision, grid_pub

P1, P2, P3 = (IpPrefix.make(f"10.0.{i}.0/24") for i in (1, 2, 3))


def entry(prefix: IpPrefix, **kw) -> PrefixEntry:
    return PrefixEntry(prefix=prefix, **kw)


def pdb(node: str, *entries: PrefixEntry, delete: bool = False) -> PrefixDatabase:
    return PrefixDatabase(
        this_node_name=node, prefix_entries=tuple(entries), delete_prefix=delete
    )


def seeded() -> PrefixState:
    """P1 from a and b (anycast), P2 from a alone."""
    ps = PrefixState()
    ps.update_prefix_db(pdb("a", entry(P1), entry(P2)))
    ps.update_prefix_db(pdb("b", entry(P1)))
    return ps


def deep(ps: PrefixState) -> dict:
    return {p: dict(per) for p, per in ps.prefixes.items()}


#: name -> (the mutation, what the live object has to read afterwards)
MUTATIONS = {
    "new_prefix": (
        lambda ps: ps.update_prefix_db(pdb("c", entry(P3))),
        lambda ps: set(ps.advertisers(P3)) == {"c"},
    ),
    "second_advertiser": (
        lambda ps: ps.update_prefix_db(pdb("b", entry(P2))),
        lambda ps: set(ps.advertisers(P2)) == {"a", "b"},
    ),
    "changed_entry": (
        lambda ps: ps.update_prefix_db(
            pdb("a", entry(P1, metrics=PrefixMetrics(path_preference=7)))),
        lambda ps: ps.advertisers(P1)["a"].metrics.path_preference == 7,
    ),
    "withdraw_one_of_two": (
        lambda ps: ps.withdraw("a", P1),
        lambda ps: set(ps.advertisers(P1)) == {"b"},
    ),
    "withdraw_the_last": (
        lambda ps: ps.withdraw("a", P2),
        lambda ps: P2 not in ps.prefixes,
    ),
    "withdraw_by_tombstone": (
        lambda ps: ps.update_prefix_db(pdb("a", entry(P2), delete=True)),
        lambda ps: P2 not in ps.prefixes,
    ),
    "withdraw_node": (
        lambda ps: ps.withdraw_node("a"),
        lambda ps: set(ps.prefixes) == {P1} and set(ps.advertisers(P1)) == {"b"},
    ),
}


@pytest.mark.parametrize("name", MUTATIONS)
def test_a_snapshot_keeps_what_it_saw_and_the_live_object_moves_on(name):
    mutate, live_reads = MUTATIONS[name]
    ps = seeded()
    snap, seen, rev = ps.snapshot(), deep(ps), ps.rev
    assert mutate(ps)  # each reports a change
    # inner dicts included: none the snapshot shares was written in place
    assert deep(snap) == seen and snap.rev == rev
    assert live_reads(ps) and ps.rev > rev
    assert deep(ps) != seen
    assert deep(ps.snapshot()) == deep(ps)


def test_two_snapshots_with_no_mutation_between_are_one_object():
    ps = seeded()
    assert not ps.snapshot_is_current
    snap = ps.snapshot()
    assert ps.snapshot_is_current
    assert ps.snapshot() is snap
    assert snap.prefixes is not ps.prefixes  # the live outer dict stays home
    assert all(snap.prefixes[p] is per for p, per in ps.prefixes.items())


def test_a_snapshot_after_a_mutation_is_a_new_one_at_the_new_rev():
    ps = seeded()
    snap = ps.snapshot()
    ps.update_prefix_db(pdb("c", entry(P3)))
    assert not ps.snapshot_is_current
    nxt = ps.snapshot()
    assert nxt is not snap
    assert (nxt.rev, snap.rev) == (ps.rev, ps.rev - 1)
    assert P3 in nxt.prefixes and P3 not in snap.prefixes
    # the untouched prefixes' dicts are shared by all three
    assert nxt.prefixes[P1] is snap.prefixes[P1] is ps.prefixes[P1]
    assert ps.snapshot() is nxt


@pytest.mark.parametrize("db", [
    pdb("c", entry(P1), delete=True),  # c never advertised P1
    pdb("a", entry(P3), delete=True),  # nobody advertises P3
    pdb("a", entry(P1), entry(P2)),    # what a advertises already
], ids=["tombstone_of_a_stranger", "tombstone_of_no_prefix", "same_again"])
def test_a_publication_that_changes_nothing_keeps_the_memo(db):
    ps = seeded()
    snap, rev = ps.snapshot(), ps.rev
    assert ps.update_prefix_db(db) == set()
    assert ps.rev == rev and ps.snapshot() is snap
    assert set(ps.prefixes) == {P1, P2}  # and no empty dict was left behind


def test_a_state_filled_directly_before_any_snapshot_is_snapshotted_whole():
    # topogen's generators fill a fresh instance's _entries without a bump
    ps = PrefixState()
    ps._entries[P1] = {"a": entry(P1)}
    snap = ps.snapshot()
    assert deep(snap) == {P1: {"a": entry(P1)}} and ps.snapshot() is snap


def test_the_election_view_built_on_a_snapshot_serves_live_and_next():
    ps = seeded()
    ids = {"a": 0, "b": 1, "c": 2}
    snap = ps.snapshot()
    view = snap.election_view(ids, interning_key=3)
    assert ps.election_view(ids, 3) is view
    assert ps.snapshot().election_view(ids, 3) is view
    ps.update_prefix_db(pdb("c", entry(P3)))
    nxt = ps.snapshot()
    view2 = nxt.election_view(ids, 3)
    assert view2 is not view and view2.gen != view.gen
    assert ps.election_view(ids, 3) is view2
    # the old snapshot is still answered for its own rev, not the cell's
    assert snap.election_view(ids, 3).gen == view.gen


# ----------------------------------------------------- through Decision


def prefix_key_of(db) -> str:
    return C.prefix_key(db.this_node_name, C.DEFAULT_AREA,
                        str(db.prefix_entries[0].prefix))


def with_metric(adj_dbs, a: str, b: str, metric: int) -> list:
    """Both ends' databases of the link a-b, at `metric`."""
    other = {a: b, b: a}
    return [
        dataclasses.replace(db, adjacencies=tuple(
            dataclasses.replace(adj, metric=metric)
            if adj.other_node_name == other[db.this_node_name] else adj
            for adj in db.adjacencies))
        for db in adj_dbs if db.this_node_name in other
    ]


async def fed_decision():
    d, adj_dbs, prefix_dbs = grid_decision(4)
    d.process_publication(grid_pub(adj_dbs, adj_key_of, 1))
    d.process_publication(grid_pub(prefix_dbs, prefix_key_of, 1))
    await d._rebuild_routes()
    return d, adj_dbs


def snapshot_counts(d) -> tuple[int, int]:
    return (d.counters.get("decision.snapshot.prefix_shared", -1),
            d.counters.get("decision.snapshot.prefix_copied", -1))


@pytest.fixture(scope="module")
def counted():
    """(shared, copied) after the first RIB, a metric-only rebuild, a
    prefix publication's rebuild, and one more metric-only rebuild."""
    async def body():
        d, adj_dbs = await fed_decision()
        counts = {"first": snapshot_counts(d)}
        d.process_publication(
            grid_pub(with_metric(adj_dbs, "node-5", "node-6", 20), adj_key_of, 2))
        await d._rebuild_routes()
        counts["metric"] = snapshot_counts(d)
        d.process_publication(
            grid_pub([pdb("node-9", entry(P3))], prefix_key_of, 1))
        await d._rebuild_routes()
        counts["prefix"] = snapshot_counts(d)
        d.process_publication(
            grid_pub(with_metric(adj_dbs, "node-5", "node-6", 10), adj_key_of, 3))
        await d._rebuild_routes()
        counts["metric_again"] = snapshot_counts(d)
        return counts, d.counters.get("decision.rebuild.failed"), set(d.rib.unicast_routes)

    return asyncio.run(body())


@pytest.mark.parametrize("after, want", [
    ("first", (0, 1)),         # both written at the first rebuild, one at 0
    ("metric", (1, 1)),        # a metric-only rebuild shares
    ("prefix", (1, 2)),        # a prefix publication copies
    ("metric_again", (2, 2)),  # and the copy serves from then on
])
def test_the_counters_say_whether_a_rebuild_shared_or_copied(counted, after, want):
    counts, failed, routes = counted
    assert counts[after] == want
    assert failed == 0 and P3 in routes


def test_a_prefix_applied_during_a_held_solve_shows_in_the_next_rib_only():
    async def body():
        d, adj_dbs = await fed_decision()
        entered, release = threading.Event(), threading.Event()
        solve, given = d._compute_and_diff, []

        def held(states, *args):
            given.append(states)
            entered.set()
            assert release.wait(30)
            return solve(states, *args)

        d._compute_and_diff = held
        d.process_publication(
            grid_pub(with_metric(adj_dbs, "node-5", "node-6", 20), adj_key_of, 2))
        rebuild = asyncio.ensure_future(d._rebuild_routes())
        while not entered.is_set():
            await asyncio.sleep(0.001)
        # the worker thread holds its snapshot; the loop applies a prefix
        d.process_publication(
            grid_pub([pdb("node-9", entry(P3))], prefix_key_of, 1))
        live = d.prefix_states[C.DEFAULT_AREA]  # drains: applied now
        applied = P3 in live.prefixes
        in_held_view = P3 in given[0][C.DEFAULT_AREA][1].prefixes
        release.set()
        await rebuild
        during = P3 in d.rib.unicast_routes
        await d._rebuild_routes()
        return (applied, in_held_view, during, P3 in d.rib.unicast_routes,
                d.counters.get("decision.rebuild.failed"), snapshot_counts(d))

    applied, in_held_view, during, after, failed, counts = asyncio.run(body())
    assert applied and not in_held_view
    assert not during and after
    assert failed == 0
    assert counts == (1, 2)  # first RIB copied, held rebuild shared, next copied
