"""The run's input frames: a pool of seeded CT slice phantoms.

A compact copy of the CT half of ``data/synthetic.make_phantom_pair``: an
elliptical skull ring, 3 to 6 soft-tissue ellipses, a ventricle pair, a
hyperdense lesion with probability 0.7, and Gaussian noise, in [-1, 1],
repeated over three channels as the served models take them.
"""
from __future__ import annotations

import numpy as np

POOL = 32  # distinct frames per run; each arrival draws one from the seed


def _ellipse(yy, xx, cy, cx, ry, rx, theta):
    c, s = np.cos(theta), np.sin(theta)
    u = (c * (xx - cx) + s * (yy - cy)) / rx
    v = (-s * (xx - cx) + c * (yy - cy)) / ry
    return u * u + v * v <= 1.0


def phantom(rng: np.random.Generator, size: int) -> np.ndarray:
    """One CT slice phantom, shape (1, size, size, 3), float32."""
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    ct = np.full((size, size), -1.0, np.float32)
    cy, cx = size / 2 + rng.uniform(-8, 8), size / 2 + rng.uniform(-8, 8)
    ry, rx = size * rng.uniform(0.36, 0.44), size * rng.uniform(0.30, 0.38)
    theta = rng.uniform(-0.3, 0.3)
    brain = _ellipse(yy, xx, cy, cx, ry * 0.92, rx * 0.92, theta)
    ct[_ellipse(yy, xx, cy, cx, ry, rx, theta) & ~brain] = 0.95
    ct[brain] = -0.1
    for _ in range(rng.integers(3, 7)):
        tcy, tcx = cy + rng.uniform(-0.5, 0.5) * ry, cx + rng.uniform(-0.5, 0.5) * rx
        tr = rng.uniform(0.08, 0.22) * min(ry, rx)
        m = _ellipse(yy, xx, tcy, tcx, tr, tr * rng.uniform(0.6, 1.4), rng.uniform(0, np.pi)) & brain
        ct[m] += (0.05, 0.12, -0.05)[rng.integers(0, 3)]
    for sgn in (-1, 1):
        m = _ellipse(yy, xx, cy, cx + sgn * 0.18 * rx, ry * 0.22, rx * 0.1, theta + sgn * 0.5) & brain
        ct[m] = -0.25
    if rng.uniform() < 0.7:
        lr = rng.uniform(0.05, 0.12) * min(ry, rx)
        m = _ellipse(yy, xx, cy + rng.uniform(-0.45, 0.45) * ry, cx + rng.uniform(-0.45, 0.45) * rx,
                     lr, lr * rng.uniform(0.7, 1.3), rng.uniform(0, np.pi)) & brain
        ct[m] = 0.35
    ct = np.clip(ct + rng.normal(0, 0.02, (size, size)).astype(np.float32), -1, 1)
    return np.repeat(ct[None, :, :, None], 3, axis=-1)


def pool(seed: int, size: int, n: int = POOL) -> list[np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 4]))
    return [phantom(rng, size) for _ in range(n)]
