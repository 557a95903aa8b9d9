"""The GF(2^8) apply kernel's share of its roofline in the background
encode: least time for the columns the window's generates handed to the
coder (k bytes in and m out per column, HBM binds) over the device time of
the encode's own operations, those with m rows out."""
from warmdown_readers import kernel_roofline_pct


def read(run: dict) -> float | None:
    facts = run["facts"]
    return kernel_roofline_pct(run, facts.get("encode_rows_out"),
                               facts.get("encode_columns"))
