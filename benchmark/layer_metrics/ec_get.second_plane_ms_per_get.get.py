"""What the second data plane costs one EC GET: `ec.get` less
`ec.get.handler` (re-write, loopback hop, aiohttp parse, middleware)."""
from stage_counters import ms_per_get, seconds


def read(run: dict) -> float | None:
    whole, handler = seconds(run, "ec.get"), seconds(run, "ec.get.handler")
    if whole is None or handler is None:
        return None
    return ms_per_get(run, whole - handler)
