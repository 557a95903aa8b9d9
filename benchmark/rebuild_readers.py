"""What the readers of a background rebuild share.

The program counts what its rebuild pipeline hands to the coder
(`seaweedfs_tpu_ec_rebuild_input_bytes_total`, `..._batches_total`, in
`ec/pipeline.stream_rebuild` beside `ec.dispatch`), times a rebuild from
the survivors found to the digests stamped (`ec.rebuild`) and each file
`ec/copy` pulls (`ec.copy`); run.py hands a reader the window's delta of
every sample as `run["counters"]`. A program without them (a parent
commit) gives every reader of them nothing to read: None, never 0.
"""

from __future__ import annotations

from stage_counters import FAMILY, total

REBUILT = "seaweedfs_tpu_ec_rebuild_input_bytes_total"
BATCHES = "seaweedfs_tpu_ec_rebuild_batches_total"


def count(run: dict, stage: str) -> float | None:
    """The window's delta of the stage's `_count`."""
    return run["counters"].get(f'{FAMILY}_count{{stage="{stage}"}}')


def ms_per(secs: float | None, n: float | None) -> float | None:
    if secs is None or not n or n <= 0:
        return None
    return 1e3 * secs / n


def ms_per_batch(run: dict, stages: tuple[str, ...]) -> float | None:
    return ms_per(total(run, stages), run["counters"].get(BATCHES))
