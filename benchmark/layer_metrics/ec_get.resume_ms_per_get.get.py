"""From an EC read's last line to the response in hand, `ec.get.resume`:
for a read that an executor thread made, the wait for the loop, heat and
the response (etag, headers, body, the write to the socket); for one the
loop's thread made itself, heat and the response alone."""
from stage_counters import ms_per_get, seconds


def read(run: dict) -> float | None:
    return ms_per_get(run, seconds(run, "ec.get.resume"))
