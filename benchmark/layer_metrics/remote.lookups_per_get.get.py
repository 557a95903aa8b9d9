"""Blocking lookups of a shard's holders at the master per GET completed
in the window (ROADMAP A2: one for the lost shard itself and one for
each other lost shard among a reconstruction's candidates). A count."""
from remote_counters import LOOKUPS, family, per_get


def read(run: dict) -> float | None:
    return per_get(run, family(run["counters"], LOOKUPS))
