"""What a repair spends pulling the survivors it lacks onto the chip's
server: S(`ec.copy`), one a file `ec/copy` pulled, request to swap-in,
over the window's rebuilds (the delta of `ec.rebuild`'s count)."""
from rebuild_readers import count, ms_per
from stage_counters import seconds


def read(run: dict) -> float | None:
    return ms_per(seconds(run, "ec.copy"), count(run, "ec.rebuild"))
