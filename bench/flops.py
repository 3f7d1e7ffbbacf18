"""Useful FLOPs per request of each served model, from its published shapes.

Each architecture counts its own (``archs/<arch>.py``, ``flops``), with the
helpers here for convolutions. Counts are 2 x the multiply-accumulates of
every convolution (the ``thop``-style count behind Ultralytics' "8.7
GFLOPs" for YOLOv8n at 640 px); norms, activations, pools and
concatenations are left out. A transposed convolution counts only the
products that land in the output it keeps. The count is of the published
model's work, whatever an implementation executes.
"""
from __future__ import annotations

from . import archs


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


def per_frame(model: dict) -> float:
    """Useful FLOPs per request of ``model``, by its architecture's ``flops``."""
    return archs.load(model["arch"]).flops(model)
