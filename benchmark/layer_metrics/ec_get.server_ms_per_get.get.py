"""The server's residence of one EC GET, `ec.get`: from the fast path's
decision to proxy to the last byte relayed, per GET completed."""
from stage_counters import ms_per_get, seconds


def read(run: dict) -> float | None:
    return ms_per_get(run, seconds(run, "ec.get"))
