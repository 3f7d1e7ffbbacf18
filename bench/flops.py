"""Useful FLOPs per frame of each served model, from its published shapes.

Counts 2 x the multiply-accumulates of every convolution (the
``thop``-style count behind Ultralytics' "8.7 GFLOPs" for YOLOv8n at
640 px); norms, activations, pools and concatenations are left out. A
transposed convolution counts only the products that land in the output
it keeps. YOLOv8's heads are counted at Ultralytics' widths (``c2 =
max(16, ch0 / 4, 4 reg_max)``, ``c3 = max(ch0, min(nc, 100))`` shared by
every level), not at the wider ones the program serves: the count is of
the published model's work, whatever an implementation executes.
"""
from __future__ import annotations

import math


def conv(h: int, c_in: int, c_out: int, k: int, stride: int = 1) -> tuple[float, int]:
    """(FLOPs, output side) of a ``k x k`` conv padded by ``k // 2`` (or by 1
    for the even pix2pix kernels) on an ``h x h`` map."""
    pad = k // 2 if k % 2 else 1
    out = (h + 2 * pad - k) // stride + 1
    return 2.0 * out * out * k * k * c_in * c_out, out


def deconv_cropped(h: int, c_in: int, c_out: int, k: int = 4) -> tuple[float, int]:
    """Stride-2 transposed conv cropped to ``2 h``: per axis ``k h`` products
    minus the ``k / 2 - 1`` on each cropped border row."""
    per_axis = k * h - 2 * (k // 2 - 1)
    return 2.0 * per_axis * per_axis * c_in * c_out, 2 * h


def pix2pix(cfg: dict) -> float:
    b, h = cfg["base"], cfg["img_size"]
    downs = [min(8 * b, b * 2**i) for i in range(int(math.log2(h)))]
    total, c = 0.0, cfg["in_channels"]
    for ch in downs:
        f, h = conv(h, c, ch, 4, 2)
        total, c = total + f, ch
    for ch in downs[:-1][::-1]:
        f, h = deconv_cropped(h, c, ch)
        total, c = total + f, 2 * ch
    return total + deconv_cropped(h, c, cfg["out_channels"])[0]


def yolov8(cfg: dict) -> float:
    def ch(x):
        return max(16, int(round(min(x, 1024) * cfg["width"] / 8)) * 8)

    def n(k):
        return max(1, round(k * cfg["depth"]))

    total = 0.0

    def cb(h, ci, co, k, s=1):
        nonlocal total
        f, out = conv(h, ci, co, k, s)
        total += f
        return out

    def c2f(h, ci, co, nb):
        c_h = co // 2
        cb(h, ci, co, 1)
        for _ in range(nb):
            cb(h, c_h, c_h, 3)
            cb(h, c_h, c_h, 3)
        cb(h, (2 + nb) * c_h, co, 1)

    c1, c2, c3, c4, c5 = (ch(c) for c in (64, 128, 256, 512, 1024))
    h = cb(cfg["img_size"], 3, c1, 3, 2)
    h = cb(h, c1, c2, 3, 2)
    c2f(h, c2, c2, n(3))
    h3 = cb(h, c2, c3, 3, 2)
    c2f(h3, c3, c3, n(6))
    h4 = cb(h3, c3, c4, 3, 2)
    c2f(h4, c4, c4, n(6))
    h5 = cb(h4, c4, c5, 3, 2)
    c2f(h5, c5, c5, n(3))
    cb(h5, c5, c5 // 2, 1)  # SPPF
    cb(h5, 2 * c5, c5, 1)
    c2f(h4, c5 + c4, c4, n(3))
    c2f(h3, c4 + c3, c3, n(3))
    cb(h3, c3, c3, 3, 2)
    c2f(h4, c3 + c4, c4, n(3))
    cb(h4, c4, c4, 3, 2)
    c2f(h5, c4 + c5, c5, n(3))
    reg, nc = cfg["reg_max"], cfg["n_classes"]
    w2, w3 = max(16, c3 // 4, 4 * reg), max(c3, min(nc, 100))
    for h, c in ((h3, c3), (h4, c4), (h5, c5)):
        cb(h, c, w2, 3)
        cb(h, w2, w2, 3)
        cb(h, w2, 4 * reg, 1)
        cb(h, c, w3, 3)
        cb(h, w3, w3, 3)
        cb(h, w3, nc, 1)
    return total


PER_FRAME = {"pix2pix_unet": pix2pix, "yolov8": yolov8}


def per_frame(model: dict) -> float:
    return PER_FRAME[model["arch"]](model)
