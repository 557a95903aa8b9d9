"""What the readers of a background warm-down share.

The program counts what its encode pipeline hands to the coder
(`seaweedfs_tpu_ec_encode_input_bytes_total`, `..._batches_total`, in
`ec/pipeline.py` beside `ec.dispatch`) and times a pass from seal to
stamp (`ec.generate`); run.py hands a reader the window's delta of every
sample as `run["counters"]` and the trace's device operations by name. A
program without the counters (a parent commit) gives every reader of them
nothing to read: None, never 0.
"""

from __future__ import annotations

import re

from reduce import gf_apply_work, least_seconds
from stage_counters import total

ENCODED = "seaweedfs_tpu_ec_encode_input_bytes_total"
BATCHES = "seaweedfs_tpu_ec_encode_batches_total"

# `%gf_apply.1 = u8[4,8388608]{...} custom-call(...)`: the kernel's name
# as `ops/rs_pallas.py` gives it, and the rows of its result
KERNEL = re.compile(r"%?gf_apply[\w.\-]* = u8\[(\d+),(\d+)\]")


def kernel_seconds(device_ops: dict, rows_out: int) -> float:
    """Device time of the apply kernel's operations with `rows_out` rows
    in their result: an encode's have m, a reconstructed interval's one."""
    out = 0.0
    for name, (_, secs) in device_ops.items():
        m = KERNEL.match(name)
        if m and int(m.group(1)) == rows_out:
            out += secs
    return out


def kernel_roofline_pct(run: dict, rows_out, columns) -> float | None:
    """Least time the chip could take for `columns` columns at k bytes in
    and `rows_out` out, over the device time of the operations that did
    that work and no other."""
    if not rows_out or not columns:
        return None
    busy = kernel_seconds(run["trace"]["device_ops"], rows_out)
    if busy <= 0:
        return None
    k = int(run["config"]["geometry"].split("+")[0])
    least, _ = least_seconds(gf_apply_work(k, rows_out, columns),
                             run["device_kind"])
    return 100.0 * least / run["chips"] / busy


def ms_per_batch(run: dict, stages: tuple[str, ...]) -> float | None:
    batches = run["counters"].get(BATCHES)
    secs = total(run, stages)
    if secs is None or not batches or batches <= 0:
        return None
    return 1e3 * secs / batches
