"""The share of declined EC reads that the serving loop answered itself
by awaiting their peer intervals on its grpc.aio call:
`VolumeServer.read_ec_needle` counts each read that
`read_needle_nowait` declined as `then="awaited"` (every interval a
local slice or on a live peer the location cache names) or
`then="executor"` (handed on). A program without the counter (a parent
commit), or a window with no declined read, gives nothing to read: None,
never 0."""

DECLINED = "seaweedfs_tpu_volume_ec_read_declined_total"


def read(run: dict) -> float | None:
    awaited, handed_on = (run["counters"].get(f'{DECLINED}{{then="{t}"}}')
                          for t in ("awaited", "executor"))
    if awaited is None or handed_on is None or awaited + handed_on <= 0:
        return None
    return 100.0 * awaited / (awaited + handed_on)
