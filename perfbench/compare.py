"""The comparison that decides `correct`: the program's tables, put into
the reference's plain form, against the reference's, key by key.

The only place that reads the program's route objects; it reads every
field of a next hop, so a field the reference does not predict fails the
comparison instead of passing unseen.
"""

from __future__ import annotations


def plain_nexthop(nh) -> tuple:
    act = nh.mpls_action
    return (
        nh.neighbor_node, nh.address, nh.if_name, int(nh.metric),
        int(nh.weight), nh.area,
        None if act is None else act.action.name,
        None if act is None else act.swap_label,
        () if act is None else tuple(act.push_labels),
    )


def plain_unicast(routes) -> dict:
    """`routes`: the program's UnicastRoute objects (a FibService table)."""
    memo: dict = {}
    out = {}
    for r in routes:
        nhs = r.nexthops
        got = memo.get(id(nhs))
        if got is None:
            got = memo[id(nhs)] = tuple(sorted(map(plain_nexthop, nhs)))
        out[str(r.dest.prefix)] = got
    return out


def plain_mpls(routes) -> dict:
    return {
        int(r.top_label): tuple(sorted(map(plain_nexthop, r.nexthops)))
        for r in routes
    }


def count_differences(got: dict, want: dict) -> tuple[int, list]:
    """Keys whose entry differs or is on one side only, and a few of them."""
    bad = [k for k in got.keys() | want.keys() if got.get(k) != want.get(k)]
    return len(bad), sorted(map(str, bad))[:3]
