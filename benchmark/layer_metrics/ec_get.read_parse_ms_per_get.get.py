"""The needle's own path per EC GET: `.ecx` search, the present
interval's read, bytes to needle (CRC inside). Needle to response is
no stage of its own: `ec.get.resume` ends with the response in hand."""
from stage_counters import ms_per_get, total


def read(run: dict) -> float | None:
    return ms_per_get(run, total(run, ("ec.get.ecx", "ec.get.parse"),
                                 ("ec.get.shard_read",)))
