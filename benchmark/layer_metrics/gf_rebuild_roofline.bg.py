"""The GF(2^8) apply kernel's share of its roofline in the background
rebuild: least time for the columns the window's rebuilds handed to the
coder (the delta of `ec_rebuild_input_bytes_total` over k; k bytes in
and one out per lost shard a column, HBM binds) over the device time of
the rebuild's own operations, those with as many rows out as shards
were lost. The arithmetic of `gf_encode_roofline.bg`."""
from rebuild_readers import REBUILT
from warmdown_readers import kernel_roofline_pct


def read(run: dict) -> float | None:
    rebuilt = run["counters"].get(REBUILT)
    if rebuilt is None:
        return None
    k = int(run["config"]["geometry"].split("+")[0])
    return kernel_roofline_pct(run, run["facts"].get("rebuild_rows_out"),
                               rebuilt / k)
