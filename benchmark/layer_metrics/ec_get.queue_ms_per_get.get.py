"""How long one EC GET waited for an executor thread, `ec.get.queue`."""
from stage_counters import ms_per_get, seconds


def read(run: dict) -> float | None:
    return ms_per_get(run, seconds(run, "ec.get.queue"))
