"""A statistic of a per-event series the driver recorded; several series
are added event by event first. args: {"series": [names], "stat": "p50"}."""

from __future__ import annotations


def read(obs: dict, args: dict) -> float | None:
    from perfbench.stats import statistic

    columns = [obs["series"].get(name) for name in args["series"]]
    if not columns or any(not c for c in columns):
        return None
    return statistic(args["stat"], [sum(row) for row in zip(*columns)], obs)
