"""The wait for the kernel, D2H and delinearize per reconstructed
interval, `ec.get.d2h_wait`."""
from stage_counters import ms_per_interval, seconds


def read(run: dict) -> float | None:
    return ms_per_interval(run, seconds(run, "ec.get.d2h_wait"))
