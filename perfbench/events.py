"""What the traffic mixes draw their events from, shared by the drivers and
the control: the node under test, the pool of links, the order of flaps,
and the warm-up that runs until nothing compiles any more."""

from __future__ import annotations

import numpy as np

from perfbench import topo


def root_of(g: topo.Graph, spec: dict) -> int:
    if "tor" in spec:
        return topo.fat_tree_tor(g, *spec["tor"])
    return int(spec["node"])


def link_pool(g: topo.Graph, kind: str, root: int) -> np.ndarray:
    """The links the events are drawn from, as rows (u, v)."""
    if kind == "fat_tree_tor_agg":
        half, k = g.meta["half"], g.meta["k"]
        own = (root - g.meta["n_core"] - g.meta["n_agg"]) // half
        return np.array([
            (topo.fat_tree_agg(g, p, a), topo.fat_tree_tor(g, p, t))
            for p in range(k) if p != own
            for a in range(half) for t in range(half)
        ])
    if kind == "any_not_at_root":
        keep = (g.src < g.dst) & (g.src != root) & (g.dst != root)
        return np.stack([g.src[keep], g.dst[keep]], axis=1)
    raise ValueError(f"unknown link pool {kind!r}")


def draw_link(pool: np.ndarray, rng) -> tuple[int, int]:
    u, v = pool[int(rng.integers(len(pool)))]
    return int(u), int(v)


def flap_sequence(pool, rng, traffic):
    """raise, raise, restore-oldest, raise, restore-oldest, ...: never
    more than `max_raised` links up at once, every link its own draw."""
    raised: list = []
    while True:
        if len(raised) < traffic["max_raised"]:
            link = draw_link(pool, rng)
            if link in raised:
                continue
            raised.append(link)
            yield link, int(traffic["raised_metric"])
        else:
            yield raised.pop(0), int(traffic["restored_metric"])


def warm_up_rounds(meter, traffic):
    """One round per event to make before the window; the caller makes the
    event inside the loop body. At least `warmup_events` rounds, and on
    until `warmup_quiet` in a row compiled nothing (a patch that lands in a
    table's overflow part, a new cone tier: each is a program of its own,
    and which event meets it first depends on the draw). Yields the round's
    number, from 1."""
    least = int(traffic["warmup_events"])
    quiet_needed = int(traffic.get("warmup_quiet", 0))
    most = int(traffic.get("warmup_max", 10 * (least + quiet_needed) + 10))
    n = quiet = 0
    while n < least or quiet < quiet_needed:
        if n >= most:
            raise RuntimeError(f"still compiling after {n} warm-up events")
        mark = meter.mark()
        n += 1
        yield n
        since = meter.since(mark)
        compiled = since["compiles"] or since["backend_compiles"]
        quiet = 0 if compiled else quiet + 1
