"""What the device's round trip moved a second of the time it took: the
k survivor rows that went up and the one row that came back, at the
width dispatched, over S(`ec.get.dispatch` + `ec.get.d2h_wait`). It
rises with the width where the round trip is launch and wait, and
flattens where it becomes bytes. A program without the counter (a
parent commit) gives nothing to read: None, never 0."""
from dispatch_counters import PADDED_BYTES
from stage_counters import total


def read(run: dict) -> float | None:
    padded = run["counters"].get(PADDED_BYTES)
    secs = total(run, ("ec.get.dispatch", "ec.get.d2h_wait"))
    if not padded or not secs or padded <= 0 or secs <= 0:
        return None
    k = int(run["config"]["geometry"].split("+")[0])
    return (k + 1) * padded / secs / (1 << 20)
