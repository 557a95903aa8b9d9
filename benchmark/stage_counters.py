"""What the readers of the program's stage counters share.

Every `observe.stage` of the program adds its seconds to one family (a
`_sum` and a `_count` a stage) on the volume server's /metrics,
`seaweedfs_tpu_ec_stage_seconds{stage="<name>"}`; run.py hands a reader
the window's delta of every sample as `run["counters"]`. A program
without the family (a parent commit) gives every reader here nothing to
read: None, never 0.
"""

from __future__ import annotations

FAMILY = "seaweedfs_tpu_ec_stage_seconds"
INTERVALS = "seaweedfs_tpu_ec_reconstruct_intervals_total"

# the stages of one EC GET that exclude one another (PERF.md has the
# table); `ec.get` encloses them all and `ec.get.handler` all but the
# second data plane's hop
REQUIRED = ("ec.get.queue", "ec.get.ecx", "ec.get.parse", "ec.get.resume")
# counted when the window had any: a GET that read no present interval,
# met no lost shard, rode no other's flight or asked no peer leaves these
# series unborn
OPTIONAL = ("ec.get.shard_read", "ec.get.flight_wait", "ec.get.peer_fetch",
            "ec.get.survivors", "ec.get.stack_pad", "ec.get.dispatch",
            "ec.get.d2h_wait")


def seconds(run: dict, stage: str) -> float | None:
    """S(stage): the window's delta of the stage's `_sum`."""
    return run["counters"].get(f'{FAMILY}_sum{{stage="{stage}"}}')


def total(run: dict, required: tuple[str, ...],
          optional: tuple[str, ...] = ()) -> float | None:
    """Sum of S over the stages; None when a required one is absent."""
    out = 0.0
    for stage in required:
        s = seconds(run, stage)
        if s is None:
            return None
        out += s
    for stage in optional:
        out += seconds(run, stage) or 0.0
    return out


def ms_per_get(run: dict, secs: float | None) -> float | None:
    gets = run["facts"].get("gets_completed")
    if secs is None or not gets:
        return None
    return 1e3 * secs / gets


def ms_per_interval(run: dict, secs: float | None) -> float | None:
    intervals = run["counters"].get(INTERVALS)
    if secs is None or not intervals or intervals <= 0:
        return None
    return 1e3 * secs / intervals
