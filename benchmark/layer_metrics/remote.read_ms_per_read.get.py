"""One interval from one peer, `ec.get.remote_read`: the gRPC stream
(or the HTTP fallback) from the call to the bytes in hand, on the
thread that fetched, waits for the GIL included."""
from stage_counters import FAMILY, seconds

STAGE = "ec.get.remote_read"


def read(run: dict) -> float | None:
    secs = seconds(run, STAGE)
    n = run["counters"].get(f'{FAMILY}_count{{stage="{STAGE}"}}')
    if secs is None or not n or n <= 0:
        return None
    return 1e3 * secs / n
