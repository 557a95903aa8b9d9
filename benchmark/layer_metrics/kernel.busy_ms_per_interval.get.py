"""Device-busy milliseconds per reconstructed interval: busy time in the
traced window over the program's own count of intervals."""
COUNTER = "seaweedfs_tpu_ec_reconstruct_intervals_total"


def read(run: dict) -> float | None:
    n = run["counters"].get(COUNTER, 0.0)
    busy = run["trace"]["busy_s"]
    if n <= 0 or busy <= 0:
        return None
    return 1e3 * busy / n
