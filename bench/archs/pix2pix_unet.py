"""Pix2Pix U-Net generator (Isola et al. 2017, pix2pix ``unet_256``; TF
tutorial layout): ``log2(img)`` stride-2 4x4 conv blocks (no norm on the
first), leaky ReLU 0.2; mirrored 4x4 stride-2 transposed convs with one
border row and column cropped (torch ``padding=1``, TF ``same``), norm,
ReLU, skip concat; a biased final transposed conv and tanh.

Its norms take per-frame statistics (``reference.norm``); its request is
one CT phantom, the default pool.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import flops as F
from ..reference import conv, deconv_cropped, norm, norm_shapes


def channels(cfg):
    b = cfg["base"]
    downs = [min(8 * b, b * 2**i) for i in range(int(math.log2(cfg["img_size"])))]
    return downs, downs[:-1][::-1]


def forward(p, x, cfg, o=None):
    downs, ups = channels(cfg)
    skips = []
    for i in range(len(downs)):
        blk = p["downs"][i]
        x = conv(x, blk["conv"]["w"], 2, 1, o=o)
        if i:
            x = norm(x, blk["bn"])
        x = jnp.where(x >= 0, x, 0.2 * x)
        skips.append(x)
    for i in range(len(ups)):
        blk = p["ups"][i]
        x = jax.nn.relu(norm(deconv_cropped(x, blk["up"]["deconv"]["w"], o=o), blk["bn"]))
        x = jnp.concatenate([x, skips[-2 - i]], axis=-1)
    f = p["final"]["deconv"]
    return jnp.tanh(deconv_cropped(x, f["w"], f["b"], o=o))


def shapes(cfg):
    downs, ups = channels(cfg)
    k, nk = 4, cfg["norm"]
    d, c_prev = [], cfg["in_channels"]
    for i, ch in enumerate(downs):
        blk = {"conv": {"w": (k, k, c_prev, ch)}}
        if i:
            blk["bn"] = norm_shapes(ch, nk)
        d.append(blk)
        c_prev = ch
    u = []
    for ch in ups:
        u.append({"up": {"deconv": {"w": (k, k, c_prev, ch)}}, "bn": norm_shapes(ch, nk)})
        c_prev = 2 * ch
    final = {"deconv": {"w": (k, k, c_prev, cfg["out_channels"]), "b": (cfg["out_channels"],)}}
    return {"downs": d, "ups": u, "final": final}


def flops(cfg: dict) -> float:
    h = cfg["img_size"]
    downs, ups = channels(cfg)
    total, c = 0.0, cfg["in_channels"]
    for ch in downs:
        f, h = F.conv(h, c, ch, 4, 2)
        total, c = total + f, ch
    for ch in ups:
        f, h = F.deconv_cropped(h, c, ch)
        total, c = total + f, 2 * ch
    return total + F.deconv_cropped(h, c, cfg["out_channels"])[0]
