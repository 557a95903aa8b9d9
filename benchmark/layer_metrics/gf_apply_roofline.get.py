"""The GF(2^8) apply kernel's share of its roofline in a degraded read:
least time for the columns the window's GETs had to have reconstructed
(k bytes in and one out per column, HBM binds) over device-busy time.
Small by nature: a 1 KB interval is padded to one 16 KiB tile."""
from reduce import roofline_pct as read  # noqa: F401
