"""95th percentile of how late the driver offered a frame after its due time."""
from bench.stats import percentile


def read(rec):
    p = percentile([a.t_offer - a.t_due for a in rec["arrivals"]], 95)
    return None if p is None else 1e3 * p
