"""Bytes the window's rebuilds handed to the coder, per second of the
traced window: the delta of `ec_rebuild_input_bytes_total` (k rows of a
batch's columns, counted where the rebuild pipeline dispatches it) over
the profiler session's seconds."""
from rebuild_readers import REBUILT


def read(run: dict) -> float | None:
    rebuilt = run["counters"].get(REBUILT)
    window_s = run["trace"]["window_s"]
    if rebuilt is None or window_s <= 0:
        return None
    return rebuilt / window_s / 1e9
