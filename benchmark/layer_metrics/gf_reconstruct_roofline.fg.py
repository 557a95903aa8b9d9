"""The GF(2^8) apply kernel's share of its roofline in the foreground's
reconstructions, beside an encode on the same device: least time for the
columns the window's GETs had to have reconstructed (k bytes in and one
out per column) over the device time of the reconstruct's own operations,
those with one row out. Read beside `gf_apply_roofline.get` of the cell
without a background, which divides by all device-busy time."""
from warmdown_readers import kernel_roofline_pct


def read(run: dict) -> float | None:
    facts = run["facts"]
    return kernel_roofline_pct(run, facts.get("rows_out"),
                               facts.get("columns_coded"))
