"""How late the open loop sent its requests (95th percentile), so that a
starved generator is not read as a fast server."""


def read(run: dict) -> float | None:
    return run["facts"].get("late_p95_ms")
