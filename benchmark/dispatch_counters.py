"""What the readers of a degraded read's dispatch counters share.

The server's host call (`ops/rs_pallas.gf_apply_pallas_host`) counts, for
each interval it hands to the device, the bytes asked for, the bytes of
the width it is dispatched at, and the dispatch by that width and by
whether a call at that width had returned before
(`..._dispatch_total{warm="yes"|"no",width="16384"|...}`); run.py hands a
reader the window's delta of every sample as `run["counters"]`. A
program without the counters (a parent commit) gives every reader of
them nothing to read: None, never 0.
"""

from __future__ import annotations

import re

INTERVAL_BYTES = "seaweedfs_tpu_ec_reconstruct_interval_bytes_total"
PADDED_BYTES = "seaweedfs_tpu_ec_reconstruct_padded_bytes_total"
DISPATCH = re.compile(r"seaweedfs_tpu_ec_reconstruct_dispatch_total\{(.*)\}")


def dispatches(counters: dict) -> tuple[float, float] | None:
    """(warm, cold) dispatches of the window over every width; None
    where the family is not there."""
    found = [(m.group(1), value) for key, value in counters.items()
             if (m := DISPATCH.fullmatch(key))]
    if not found:
        return None
    cold = sum(v for labels, v in found if 'warm="no"' in labels)
    return sum(v for _, v in found) - cold, cold
