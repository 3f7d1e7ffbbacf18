"""The comparison that decides ``correct``.

Rounding at the stated precision (``precision``: float32 values whose
conv operands are rounded to bfloat16, products accumulated in float32,
which is what a TPU's default matmul precision computes) leaves every
served output with a scatter of its own: the deep networks amplify the
difference between two roundings of nearly equal values, so two runs of
the reference itself on a frame and on the same frame nudged by a
millionth lie as far apart as the program lies from either. A single
reference run therefore cannot tell the program from a computation in
bfloat16, which scatters only about three times as far.

So the reference (``reference.py``) runs ``SPREAD_RUNS`` times on each
sampled frame, each time on the frame nudged by ``NUDGE`` in another
direction, conv operands rounded to the stated type and products summed
under ``highest``. Per model the number compared is ``gap_over_spread``,
the served outputs' scatter in units of the runs' own: with ``gap`` the
root mean square, over the sampled arrivals that completed, of the served
output's relative L2 gap to the mean of the runs, and ``spread`` the root
mean square of the runs' relative spread about their mean, it is
``sqrt(gap**2 / spread**2 - 1 / SPREAD_RUNS)``; the subtracted term is
what the mean of ``SPREAD_RUNS`` runs itself scatters. A program that
rounds as the configuration states reads about 1, one that computes in
bfloat16 more than three. A frame whose output is missing, misshapen or
not finite reads infinitely far off, and a model with no frame compared
fails.
"""
from __future__ import annotations

import json
import math

import numpy as np

from . import reference

BLOCK = 8  # frames per reference call
SPREAD_RUNS = 4  # reference runs per frame, each on the frame nudged another way
NUDGE = 1e-6  # relative size of a nudge: it changes no answer, only which values round up
_JITTED: dict = {}  # one compiled reference per (model, dtype, precision)


def nudged(frame: np.ndarray, k: int) -> np.ndarray:
    """``frame`` times ``1 + NUDGE * z`` with ``z`` standard normal, drawn from ``k``."""
    z = np.random.default_rng(np.random.SeedSequence([k, 5])).standard_normal(frame.shape)
    return (frame * (1.0 + NUDGE * z)).astype(frame.dtype)


def reference_outputs(models, weights, frames, wanted: dict, dtype, operands=None,
                      precision: str = "highest", nudge: int | None = None) -> dict:
    """``{(model, pool index): output}`` of the reference, computed in
    ``dtype`` with conv operands rounded to ``operands`` (None: not
    rounded) under matmul ``precision``, in blocks of ``BLOCK`` frames;
    with ``nudge`` on each frame nudged by the ``nudge``-th draw."""
    import jax
    import jax.numpy as jnp

    out = {}
    for mi, ks in wanted.items():
        if not ks:
            continue
        cfg, fwd = models[mi], reference.FORWARD[models[mi]["arch"]]
        w = jax.tree.map(lambda a: a.astype(dtype), weights[mi])
        key = (json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name, str(operands), precision)
        if key not in _JITTED:

            def f(p, x, cfg=cfg, fwd=fwd):
                y = fwd(p, x.astype(dtype), cfg, operands)
                return jax.tree.map(lambda a: a.astype(jnp.float32), y)

            _JITTED[key] = jax.jit(f)
        f = _JITTED[key]
        ks = sorted(ks)
        with jax.default_matmul_precision(precision):
            for b in range(0, len(ks), BLOCK):
                blk = ks[b:b + BLOCK]
                pad = blk + [blk[0]] * (BLOCK - len(blk))  # one compiled shape
                x = np.concatenate([frames[k] if nudge is None else nudged(frames[k], nudge) for k in pad])
                y = jax.device_get(f(w, x))
                for j, k in enumerate(blk):
                    out[mi, k] = jax.tree.map(lambda a, j=j: a[j:j + 1], y)
    return out


def rel_l2(got, want) -> float:
    """Relative L2 gap over all leaves of one frame's output."""
    import jax

    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    if len(gl) != len(wl):
        return math.inf
    num = den = 0.0
    for g, w in zip(gl, wl):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if g.shape != w.shape or not np.isfinite(g).all():
            return math.inf
        num += float(((g - w) ** 2).sum())
        den += float((w**2).sum())
    return math.sqrt(num / den) if den > 0 else math.inf


def sampled(sched, model_of, mi: int) -> list:
    return [a for a in sched if a.sampled and a.done and model_of[a.stream] == mi]


def _mean(outs):
    import jax

    return jax.tree.map(lambda *a: np.mean(np.stack([np.asarray(x, np.float64) for x in a]), 0), *outs)


def compare(config, models, weights, frames, sched, model_of) -> dict:
    """``{"<model>.gap_over_spread": {"value", "limit", "frames", "gap",
    "spread"}}`` for every model; ``gap`` and ``spread`` are the two root
    mean squares whose ratio is the value."""
    import jax.numpy as jnp

    prec = config["precision"]
    wanted = {mi: {a.pool for a in sampled(sched, model_of, mi)} for mi in range(len(models))}
    runs = [reference_outputs(models, weights, frames, wanted, jnp.dtype(prec["dtype"]),
                              jnp.dtype(prec["conv_operands"]), nudge=k) for k in range(SPREAD_RUNS)]
    checks = {}
    for mi, m in enumerate(models):
        smp = sampled(sched, model_of, mi)
        means = {k: _mean([r[mi, k] for r in runs]) for k in wanted[mi]}
        spread = {k: sum(rel_l2(r[mi, k], means[k]) ** 2 for r in runs) / (SPREAD_RUNS - 1)
                  for k in wanted[mi]}
        gap2 = sum(rel_l2(a.output, means[a.pool]) ** 2 for a in smp)
        spread2 = sum(spread[a.pool] for a in smp)
        value = math.sqrt(max(0.0, gap2 / spread2 - 1 / SPREAD_RUNS)) if smp and spread2 > 0 else math.inf
        checks[f"{m['name']}.gap_over_spread"] = {
            "value": value, "limit": config["limits"][m["name"]], "frames": len(smp),
            "gap": math.sqrt(gap2 / len(smp)) if smp else math.inf,
            "spread": math.sqrt(spread2 / len(smp)) if smp else math.inf}
    return checks


def control_outputs(models, weights, frames, sched, model_of, dtype) -> None:
    """Put the reference computed in ``dtype`` in the program's place: every
    sampled arrival's output becomes the lower-precision reference's."""
    wanted = {mi: {a.pool for a in sampled(sched, model_of, mi)} for mi in range(len(models))}
    refs = reference_outputs(models, weights, frames, wanted, dtype, precision="default")
    for mi in range(len(models)):
        for a in sampled(sched, model_of, mi):
            a.output = refs[mi, a.pool]


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
