"""What a batch of the background encode spends at the disk and beside
it: S(`ec.read` + `ec.write` + `ec.digest` + `ec.fsync`) over the
window's batches: the feed's read, the hand-off to the fourteen writers,
the inline digests, and each pass's drain, fsync and close."""
from warmdown_readers import ms_per_batch


def read(run: dict) -> float | None:
    return ms_per_batch(run, ("ec.read", "ec.write", "ec.digest",
                              "ec.fsync"))
