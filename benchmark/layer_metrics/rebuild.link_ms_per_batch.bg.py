"""What a batch of the background rebuild spends at the host link:
S(`ec.dispatch` + `ec.kernel`) over the window's rebuild batches (the
delta of `ec_rebuild_batches_total`): H2D and launch on the dispatching
thread, and the materializer's wait for the rebuilt rows (kernel +
D2H). The cell runs no encode in its window, so both stages are the
rebuild's."""
from rebuild_readers import ms_per_batch


def read(run: dict) -> float | None:
    return ms_per_batch(run, ("ec.dispatch", "ec.kernel"))
