"""How unevenly the cell's chips are loaded: the most minus the least device
busy time of a chip in the traced tail, over the mean, in percent."""


def read(rec):
    busy = (rec["trace"] or {}).get("busy_s")
    if not busy:
        return None
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean > 0 else None
