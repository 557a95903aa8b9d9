"""H2D + launch on the host per reconstructed interval,
`ec.get.dispatch`."""
from stage_counters import ms_per_interval, seconds


def read(run: dict) -> float | None:
    return ms_per_interval(run, seconds(run, "ec.get.dispatch"))
