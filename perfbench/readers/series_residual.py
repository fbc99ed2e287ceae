"""What of one per-event series no other series accounts for: a statistic
of `of` less the sum of `less`, event by event. A series the driver did
not record (an older program without that span) leaves the metric out.
args: {"of": name, "less": [names], "stat": "p50"}."""

from __future__ import annotations


def read(obs: dict, args: dict) -> float | None:
    from perfbench.stats import statistic

    whole = obs["series"].get(args["of"])
    parts = [obs["series"].get(name) for name in args["less"]]
    if not whole or not parts or any(not p for p in parts):
        return None
    rest = [w - sum(row) for w, row in zip(whole, zip(*parts))]
    return statistic(args["stat"], rest, obs)
