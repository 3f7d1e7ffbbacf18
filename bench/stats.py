"""Arithmetic shared by the metric readers."""
from __future__ import annotations

import math


def percentile(samples, pct: float) -> float | None:
    """Nearest-rank percentile, ``pct`` in [0, 100]; None when empty."""
    s = sorted(samples)
    if not s:
        return None
    return s[min(len(s), max(1, math.ceil(pct / 100.0 * len(s)))) - 1]


def completed(rec) -> list:
    """Arrivals due in the window that completed."""
    return [a for a in rec["arrivals"] if a.done]


def in_deadline(rec) -> list:
    return [a for a in completed(rec) if a.latency_due_s <= rec["deadline_s"]]


def issue_ms_per_frame(rec) -> float | None:
    """Host time of the executor's ticks outside ``block_until_ready``,
    over the window's ticks (those before the traced tail, in a traced
    run), per completed frame."""
    n = len(completed(rec))
    if not n:
        return None
    return 1e3 * sum(t.wall_s - t.blocked_s for t in rec["ticks"]) / n


def busy_ms_per_frame(rec) -> float | None:
    """Device busy time in the traced tail, summed over the cell's chips,
    per frame completed inside it."""
    tr, span = rec["trace"], rec["traced_s"]
    if tr is None:
        return None
    n = sum(1 for a in rec["arrivals"] + rec["tail"]
            if a.done and span[0] <= a.t_offer + a.latency_s <= span[1])
    return 1e3 * sum(tr["busy_s"]) / n if n else None


def idle_pct(rec) -> float | None:
    return None if rec["trace"] is None else 100.0 * rec["trace"]["idle_share"]
