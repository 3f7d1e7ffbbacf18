"""A toy architecture for the benchmark's tests, outside ``bench/``.

Its request is an image and an integer box, like a promptable
segmenter's: a patch embedding (4x4, stride 4) plus a table of position
embeddings that only this module's ``INIT`` knows how to draw, the tokens
outside the box zeroed, GELU, and a 1x1 head giving one logit per token and
the box's mean logit. The tests put this directory on the module search
path and name ``toy_box_arch`` as a model's ``arch``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import conv

# a position table drawn N(0, 0.5): zeros would leave it untested
INIT = {"pos_table": lambda z: 0.5 * z}


def shapes(cfg):
    g, w, p = cfg["img_size"] // cfg["patch"], cfg["width"], cfg["patch"]
    return {"embed": {"w": (p, p, 1, w), "b": (w,)}, "pos": {"pos_table": (g, g, w)},
            "head": {"w": (1, 1, w, 1), "b": (1,)}}


def forward(p, req, cfg, o=None):
    k = cfg["patch"]
    x = conv(req["image"], p["embed"]["w"], k, 0, p["embed"]["b"], o=o) + p["pos"]["pos_table"]
    g = x.shape[1]
    c = (jnp.arange(g) + 0.5) * k  # token centres in pixels
    box = req["box"][:, :, None, None]  # x0, y0, x1, y1
    inside = ((c[None, None, :] >= box[:, 0]) & (c[None, None, :] < box[:, 2])
              & (c[None, :, None] >= box[:, 1]) & (c[None, :, None] < box[:, 3]))
    logits = conv(jax.nn.gelu(x * inside[..., None]), p["head"]["w"], b=p["head"]["b"], o=o)
    n = jnp.maximum(inside.sum(axis=(1, 2)), 1)
    return {"logits": logits, "score": (logits[..., 0] * inside).sum(axis=(1, 2)) / n}


def flops(cfg):
    g, w, p = cfg["img_size"] // cfg["patch"], cfg["width"], cfg["patch"]
    return 2.0 * g * g * (p * p * w + w)


def requests(seed, cfg, n):
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 99]))
    s = cfg["img_size"]
    out = []
    for _ in range(n):
        x0, y0 = rng.integers(0, s // 2, 2)
        x1, y1 = x0 + rng.integers(s // 4, s // 2 + 1), y0 + rng.integers(s // 4, s // 2 + 1)
        out.append({"image": rng.standard_normal((1, s, s, 1)).astype(np.float32),
                    "box": np.array([[x0, y0, x1, y1]], np.int32)})
    return out


def kernels(cfg):
    """The patch embedding: its convolution in the toy's executable."""
    g, w, p = cfg["img_size"] // cfg["patch"], cfg["width"], cfg["patch"]
    return {"embed": (lambda module, op: module.startswith("jit_toy.") and "convolution" in op,
                      2.0 * g * g * p * p * w, 4.0 * (cfg["img_size"] ** 2 + p * p * w + g * g * w))}
