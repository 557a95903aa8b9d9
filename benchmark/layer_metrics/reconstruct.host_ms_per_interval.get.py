"""Host work before the device per reconstructed interval: the k
survivor reads and the stack and pad copies, and the peer asked for the
lost shard itself first (`ec.get.peer_fetch`, where a shard reader is
plugged in)."""
from stage_counters import ms_per_interval, total


def read(run: dict) -> float | None:
    return ms_per_interval(run, total(
        run, ("ec.get.survivors", "ec.get.stack_pad"),
        ("ec.get.peer_fetch",)))
