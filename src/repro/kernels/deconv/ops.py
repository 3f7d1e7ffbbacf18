"""Jit wrapper for the phase-decomposed deconv kernel.

Compiled with Mosaic on TPU and interpreted elsewhere
(``kernels.backend``); ``ref.py`` is the pure-jnp oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import deconv2d_pallas
from .ref import deconv2d_ref


def deconv2d(x, w, b=None, stride: int = 2, padding: int = 1, use_pallas: bool = True, tile_h: int = 8):
    """Hardware-aware transposed conv (the Pix2Pix upsample op).

    The Pallas path is specialized to the paper's configuration
    (k=4, stride=2, torch padding=1); other configs fall back to the
    XLA reference implementation.
    """
    k = w.shape[0]
    if use_pallas and k == 4 and stride == 2 and padding == 1:
        y = deconv2d_pallas(x, w, tile_h=tile_h)
        if b is not None:
            y = y + b.astype(y.dtype)
        return y
    return deconv2d_ref(x, w, b=b, stride=stride, padding=padding)
