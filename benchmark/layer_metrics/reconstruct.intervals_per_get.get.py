"""Intervals reconstructed per GET completed in the window: a count."""
COUNTER = "seaweedfs_tpu_ec_reconstruct_intervals_total"


def read(run: dict) -> float | None:
    gets = run["facts"].get("gets_completed")
    if not gets or COUNTER not in run["counters"]:
        return None
    return run["counters"][COUNTER] / gets
