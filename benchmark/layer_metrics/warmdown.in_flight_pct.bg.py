"""Share of the traced window in which a generate was in flight:
S(`ec.generate`), the stage that encloses a pass from seal to stamp, over
the profiler session's seconds."""
from stage_counters import seconds


def read(run: dict) -> float | None:
    in_flight = seconds(run, "ec.generate")
    window_s = run["trace"]["window_s"]
    if in_flight is None or window_s <= 0:
        return None
    return 100.0 * in_flight / window_s
