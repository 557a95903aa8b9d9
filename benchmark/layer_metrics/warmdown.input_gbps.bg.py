"""Bytes the window's generates handed to the coder, per second of the
traced window: the delta of `ec_encode_input_bytes_total` (counted where
the pipeline dispatches a batch) over the profiler session's seconds."""
from warmdown_readers import ENCODED


def read(run: dict) -> float | None:
    encoded = run["counters"].get(ENCODED)
    window_s = run["trace"]["window_s"]
    if encoded is None or window_s <= 0:
        return None
    return encoded / window_s / 1e9
