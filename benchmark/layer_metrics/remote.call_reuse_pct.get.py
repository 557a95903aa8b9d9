"""The share of remote reads that found their peer's call prepared:
`VolumeServer._make_shard_reader` keeps one gRPC channel a peer with the
one `VolumeEcShardRead` call made from it, and counts each read as
`reused` (both were there) or `built` (this read dialled and prepared
them). A program without the counter (a parent commit) gives nothing to
read: None, never 0."""

CALLS = "seaweedfs_tpu_volume_ec_peer_call_total"


def read(run: dict) -> float | None:
    reused, built = (run["counters"].get(f'{CALLS}{{result="{r}"}}')
                     for r in ("reused", "built"))
    if reused is None or built is None or reused + built <= 0:
        return None
    return 100.0 * reused / (reused + built)
