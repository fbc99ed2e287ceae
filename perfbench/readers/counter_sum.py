"""The growth of some counters over the window, added up: a plain count,
0 included. args: {"counters": [names]}."""

from __future__ import annotations


def read(obs: dict, args: dict) -> float | None:
    found = [obs["counters"][c] for c in args["counters"] if c in obs["counters"]]
    if not found:
        return None
    return float(sum(found))
