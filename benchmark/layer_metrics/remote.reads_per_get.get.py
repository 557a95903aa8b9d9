"""Intervals taken from peers per GET completed in the window: one for a
plain interval on a peer's shard, the survivors that are not local for
a reconstruction. A count."""
from remote_counters import READS, family, per_get


def read(run: dict) -> float | None:
    return per_get(run, family(run["counters"], READS))
