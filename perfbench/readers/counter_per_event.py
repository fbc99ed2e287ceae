"""A counter's growth over the window, per completed event.
args: {"counter": name}."""

from __future__ import annotations


def read(obs: dict, args: dict) -> float | None:
    if args["counter"] not in obs["counters"] or not obs["events"]:
        return None
    return obs["counters"][args["counter"]] / obs["events"]
