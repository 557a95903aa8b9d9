"""Device idle share of the traced window, from the profiler trace."""
from reduce import idle_pct as read  # noqa: F401
