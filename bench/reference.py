"""Shared parts of the plain references, in ``jax.numpy`` and ``jax.lax`` alone.

Each architecture's reference is its module under ``archs/`` (``forward``,
``shapes``), written from the published architecture, independent of the
program. This module holds the layers they share and ``make_weights``,
which makes every model's parameters from one seed.

Norm layers take per-frame statistics over (H, W) at inference, a
departure each served configuration runs: that is the pix2pix original's
batch norm in training mode at batch 1 and the instance norm of the
batch-independent variant; the served batch-norm models run one frame per
flight, so their statistics are per frame too.

Parameters are nested dicts shaped as the served program holds them, so
that the benchmark can make one set from a seed and give it to both.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import archs

DN = ("NHWC", "HWIO", "NHWC")
EPS = 1e-5


# -- layers -------------------------------------------------------------------


def _operands(x, w, o):
    """Conv operands as the chip's MXU takes them: rounded to ``o`` (None:
    as they are). Products of ``bfloat16`` values are exact in float32, so
    under ``highest`` precision the sum is the float32 accumulation of the
    rounded operands' products: what default precision computes on a TPU."""
    return (x, w) if o is None else (x.astype(o).astype(x.dtype), w.astype(o).astype(x.dtype))


def conv(x, w, stride=1, pad=0, b=None, o=None):
    x, w = _operands(x, w.astype(x.dtype), o)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)], dimension_numbers=DN
    )
    return y if b is None else y + b.astype(x.dtype)


def deconv_cropped(x, w, b=None, o=None):
    """k x k stride-2 transposed conv, one border row and column cropped
    from each side: output side ``2 * input``."""
    k = w.shape[0]
    x, w = _operands(x, w.astype(x.dtype), o)
    y = lax.conv_general_dilated(
        x, w, (1, 1), [(k - 2, k - 2), (k - 2, k - 2)],
        lhs_dilation=(2, 2), dimension_numbers=DN,
    )
    return y if b is None else y + b.astype(x.dtype)


def norm(x, p):
    """Per-frame, per-channel statistics over (H, W)."""
    mean = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(1, 2), keepdims=True)
    y = (x - mean) * lax.rsqrt(var + EPS)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def max_pool5(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 5, 5, 1), (1, 1, 1, 1), [(0, 0), (2, 2), (2, 2), (0, 0)]
    )


def upsample2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def norm_shapes(c, norm_kind):
    if norm_kind == "batch":
        return {"scale": (c,), "bias": (c,), "moving_mean": (c,), "moving_var": (c,)}
    return {"scale": (c,), "bias": (c,)}


# -- weights ----------------------------------------------------------------------

# how a leaf's standard normal draw ``z`` becomes its value, by leaf name;
# a name in no table is drawn as zeros
INIT = {
    "w": lambda z: z * math.sqrt(2.0 / math.prod(z.shape[:-1])),  # He-normal over fan-in
    "scale": lambda z: 1.0 + 0.1 * z,
    "b": lambda z: 0.1 * z,
    "bias": lambda z: 0.1 * z,
    "moving_var": lambda z: jnp.ones(z.shape, jnp.float32),
}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def _rules(model: dict) -> dict:
    """``INIT`` and the rules of the model's architecture for names beyond it."""
    own = getattr(archs.load(model["arch"]), "INIT", {})
    if set(own) & set(INIT):
        raise ValueError(f"{model['arch']}.INIT redefines {sorted(set(own) & set(INIT))}")
    return {**own, **INIT}


_MAKERS: dict = {}  # one compiled weight maker per set of models


def make_weights(models: list[dict], seed: int, dtype=jnp.float32):
    """Every model's parameters from one seed, made on the device in one
    jitted call from one normal draw: conv weights He-normal, conv biases
    and norm shifts ``N(0, 0.1)``, norm scales ``1 + N(0, 0.1)``, running
    statistics 0 and 1 (the served norms never read them); leaves of
    other names by their architecture's ``INIT``, else zeros."""
    shapes = [archs.load(m["arch"]).shapes(m) for m in models]
    rules = [_rules(m) for m in models]
    flat, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    sizes = [math.prod(shape) for _, shape in flat]

    def make(key):
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, off = [], 0
        for (path, shape), n in zip(flat, sizes):
            rule = rules[path[0].idx].get(getattr(path[-1], "key", None))
            v = z[off:off + n].reshape(shape)
            off += n
            v = jnp.zeros(shape, jnp.float32) if rule is None else rule(v)
            out.append(v.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    spec = json.dumps([models, jnp.dtype(dtype).name], sort_keys=True)
    if spec not in _MAKERS:
        _MAKERS[spec] = jax.jit(make)
    return _MAKERS[spec](key)
