"""Executor ticks' host time outside block_until_ready per completed frame (TickStats)."""
from bench.stats import issue_ms_per_frame as read  # noqa: F401
