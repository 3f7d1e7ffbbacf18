"""Two-state Markov-modulated Poisson arrivals with the count fixed.

Quiet at rate ``q``, bursting at ``burst_factor * q``, with exponential
dwells of mean ``mean_quiet_s`` and ``mean_burst_s``, quiet first. The
dwells are rescaled to their expected shares of the window, and each
state's expected count of arrivals is placed uniformly over its dwells."""
from __future__ import annotations

import numpy as np


def times(rng, rate_hz: float, seconds: float, p: dict) -> np.ndarray:
    f, mb, mq = float(p["burst_factor"]), float(p["mean_burst_s"]), float(p["mean_quiet_s"])
    share_b = mb / (mb + mq)
    quiet_hz = rate_hz / (1.0 - share_b + f * share_b)
    dwells, t, burst = [], 0.0, False  # quiet first: bursts arrive mid-run
    while t < seconds or not any(b for b, _ in dwells):
        d = rng.exponential(mb if burst else mq)
        dwells.append((burst, d))
        t += d
        burst = not burst
    total = {b: sum(d for s, d in dwells if s == b) for b in (False, True)}
    target = {False: seconds * (1.0 - share_b), True: seconds * share_b}
    out, start = [], 0.0
    n = {False: round(quiet_hz * target[False]), True: round(f * quiet_hz * target[True])}
    spans = {False: [], True: []}
    for b, d in dwells:
        d *= target[b] / total[b]
        spans[b].append((start, d))
        start += d
    for b in (False, True):
        # place the state's arrivals uniformly over the union of its dwells
        u = np.sort(rng.uniform(0.0, target[b], n[b]))
        edges = np.cumsum([0.0] + [d for _, d in spans[b]])
        k = np.clip(np.searchsorted(edges, u, side="right") - 1, 0, len(spans[b]) - 1)
        out.append(np.array([spans[b][j][0] for j in k]) + (u - edges[k]))
    return np.sort(np.concatenate(out))
