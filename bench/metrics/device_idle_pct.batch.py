"""Device idle share of the traced window: 1 - (union of the intervals
in which an operation ran on the device / the window), the highest over
the cell's chips."""
from bench.tracing import worst_idle_pct as read  # noqa: F401
