"""Share of the traced window in which a repair was in flight on the
chip's server: S(`ec.rebuild` + `ec.copy`), the rebuild from the
survivors found to the digests stamped and each survivor file pulled
from a peer, over the profiler session's seconds."""
from stage_counters import total


def read(run: dict) -> float | None:
    in_flight = total(run, ("ec.rebuild", "ec.copy"))
    window_s = run["trace"]["window_s"]
    if in_flight is None or window_s <= 0:
        return None
    return 100.0 * in_flight / window_s
