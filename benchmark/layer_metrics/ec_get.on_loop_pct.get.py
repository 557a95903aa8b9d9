"""The share of EC reads that the loop's thread made itself, with no
hand-off to an executor thread and back: `VolumeServer.read_ec_needle`
counts each read as `served` (every interval in a mapped shard file of
this server) or `declined` (handed on). A program without the counter (a
parent commit) gives nothing to read: None, never 0."""

NOWAIT = "seaweedfs_tpu_volume_ec_read_nowait_total"


def read(run: dict) -> float | None:
    served, declined = (run["counters"].get(f'{NOWAIT}{{result="{r}"}}')
                        for r in ("served", "declined"))
    if served is None or declined is None or served + declined <= 0:
        return None
    return 100.0 * served / (served + declined)
