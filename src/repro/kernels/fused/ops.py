"""jit wrappers for the fused serving blocks.

Every call runs the Pallas kernel (compiled on TPU, interpreted
elsewhere — ``kernels.backend``); batch norm at B > 1 takes its batch
statistics inside the kernel, so no shape falls back to the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import conv_block_pallas, deconv_block_pallas, sppf_pyramid_pallas


def _affine(x, b, gamma, beta, cout):
    f32 = jnp.float32
    b = jnp.zeros((cout,), f32) if b is None else b
    gamma = jnp.ones((cout,), f32) if gamma is None else gamma
    beta = jnp.zeros((cout,), f32) if beta is None else beta
    return b, gamma, beta


@functools.partial(jax.jit, static_argnames=("stride", "padding", "norm", "groups", "act", "eps"))
def conv_block(
    x,
    w,
    b=None,
    gamma=None,
    beta=None,
    stride: int = 1,
    padding: int = 0,
    norm: str = "batch",
    groups: int = 1,
    act: str = "silu",
    eps: float = 1e-5,
):
    """Fused conv(+bias)+norm+act: (B, H, W, Cin) -> (B, Ho, Wo, Cout)."""
    b, gamma, beta = _affine(x, b, gamma, beta, w.shape[-1])
    return conv_block_pallas(
        x, w, b, gamma, beta, stride=stride, padding=padding, norm=norm,
        groups=groups, act=act, eps=eps,
    )


@functools.partial(jax.jit, static_argnames=("norm", "groups", "act", "eps"))
def deconv_block(
    x,
    w,
    b=None,
    gamma=None,
    beta=None,
    norm: str = "batch",
    groups: int = 1,
    act: str = "relu",
    eps: float = 1e-5,
):
    """Fused k=4/s=2 deconv + crop (+bias) + norm + act: -> (B, 2H, 2W, Cout)."""
    b, gamma, beta = _affine(x, b, gamma, beta, w.shape[-1])
    return deconv_block_pallas(x, w, b, gamma, beta, norm=norm, groups=groups, act=act, eps=eps)


@functools.partial(jax.jit, static_argnames=("window", "reps"))
def sppf_pyramid(x, window: int = 5, reps: int = 3):
    """Fused SPPF pool pyramid + concat: (B, H, W, C) -> (B, H, W, (reps+1)*C).

    Max/concat only — exact at any batch."""
    return sppf_pyramid_pallas(x, window=window, reps=reps)
