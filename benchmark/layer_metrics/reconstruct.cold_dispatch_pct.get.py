"""The share of the window's dispatches that met a width no call had
returned from yet, so that they compiled inside a GET or waited for the
compile: `seaweedfs_tpu_ec_reconstruct_dispatch_total{warm="no"}` over
all of the family. 0 on a server whose warm-up ended before the window.
A program without the counter (a parent commit) gives nothing to read:
None, never 0."""
from dispatch_counters import dispatches


def read(run: dict) -> float | None:
    found = dispatches(run["counters"])
    if found is None or sum(found) <= 0:
        return None
    return 100.0 * found[1] / sum(found)
