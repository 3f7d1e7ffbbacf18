"""Device busy union in the traced window, summed over chips, per frame completed in it."""
from bench.stats import busy_ms_per_frame as read  # noqa: F401
