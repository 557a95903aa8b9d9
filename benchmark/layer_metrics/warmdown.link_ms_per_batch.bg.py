"""What a batch of the background encode spends at the host link:
S(`ec.dispatch` + `ec.kernel`) over the window's batches: H2D and launch
on the dispatching thread, and the materializer's wait for the parity
(kernel + D2H)."""
from warmdown_readers import ms_per_batch


def read(run: dict) -> float | None:
    return ms_per_batch(run, ("ec.dispatch", "ec.kernel"))
