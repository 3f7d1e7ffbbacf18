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


def tail_completed(rec, model: str | None = None) -> list:
    """Frames (of ``model``, a configuration's model name, or of every
    model) that completed inside the traced tail."""
    lo, hi = rec["traced_s"]
    mi = None if model is None else [m["name"] for m in rec["models"]].index(model)
    return [a for a in rec["arrivals"] + rec["tail"]
            if a.done and lo <= a.t_offer + a.latency_s <= hi and (mi is None or rec["model_of"][a.stream] == mi)]


def busy_ms_per_frame(rec) -> float | None:
    """Device busy time in the traced tail, summed over the cell's chips,
    per frame completed inside it."""
    if rec["trace"] is None:
        return None
    n = len(tail_completed(rec))
    return 1e3 * sum(rec["trace"]["busy_s"]) / n if n else None


def span_self_ms_per_frame(rec, name: str) -> float | None:
    """Self time of the program's span ``name`` in the traced tail, summed
    over replicas, per frame completed inside it."""
    c = (rec.get("spans") or {}).get("counters", {}).get(name)
    n = len(tail_completed(rec)) if c else 0
    return 1e3 * c["self_s"] / n if n else None


def model_device_ms_per_frame(rec, model: str) -> float | None:
    """Device time of ``model``'s segment executables in the traced tail,
    summed over chips, per frame of that model completed inside it."""
    sp = rec.get("spans")
    if sp is None:
        return None
    prefix = rec["executables"][model]
    t = sum(s for plane in sp["module_time"].values() for mod, s in plane["modules"].items()
            if mod.startswith(prefix))
    n = len(tail_completed(rec, model))
    return 1e3 * t / n if t and n else None


def idle_host_busy_pct(rec) -> float | None:
    sp = rec.get("spans")
    return None if sp is None else sp["idle_host_busy_pct"]


def idle_pct(rec) -> float | None:
    return None if rec["trace"] is None else 100.0 * rec["trace"]["idle_share"]
