"""What the readers of the program's remote-read counters share.

`VolumeServer._make_shard_reader` counts every interval it took from a
peer (`..._ec_remote_shard_reads_total{via="grpc"|"http"}`) and every
blocking lookup of a shard's holders at the master
(`..._ec_shard_location_lookups_total{result="holder"|"none"}`); run.py
hands a reader the window's delta of every sample as `run["counters"]`.
A program without a family (a parent commit) gives nothing to read:
None, never 0.
"""

from __future__ import annotations

READS = "seaweedfs_tpu_volume_ec_remote_shard_reads_total"
LOOKUPS = "seaweedfs_tpu_volume_ec_shard_location_lookups_total"


def family(counters: dict, name: str) -> float | None:
    """A family summed over its label sets; None where it is absent."""
    got = [v for key, v in counters.items() if key.startswith(name + "{")]
    return sum(got) if got else None


def per_get(run: dict, n: float | None) -> float | None:
    gets = run["facts"].get("gets_completed")
    if n is None or not gets:
        return None
    return n / gets
