"""Bytes dispatched over bytes asked for, per reconstructed interval: the
server's host call pads an interval to a power of two of 16 KiB tiles,
and counts both (`ops/rs_pallas.py`, beside `ec.get.dispatch`). 1.0 is
no padding; a 1 KB interval reads 16, a 64 KB record's 65,576 bytes read
2. A program without the counters (a parent commit) gives nothing to
read: None, never 0."""
from dispatch_counters import INTERVAL_BYTES, PADDED_BYTES


def read(run: dict) -> float | None:
    asked = run["counters"].get(INTERVAL_BYTES)
    padded = run["counters"].get(PADDED_BYTES)
    if not asked or padded is None or asked <= 0:
        return None
    return padded / asked
