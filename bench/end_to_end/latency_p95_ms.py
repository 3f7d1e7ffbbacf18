"""95th percentile latency, due time to completion, of the frames due in the window that completed."""
from bench.stats import completed, percentile


def read(rec):
    p = percentile([a.latency_due_s for a in completed(rec)], 95)
    return None if p is None else 1e3 * p
