"""Pallas TPU kernel: phase-decomposed stride-2 transposed conv + fused crop.

GPU implementations scatter each input pixel into a k x k output window —
a memory-bound pattern with no MXU analogue. The TPU-native adaptation
decomposes the (k=4, stride=2, torch-padding=1) deconv by *output parity
phase*: with (a, b) = output (row, col) parity, every output pixel is

    y[2u'+rp, 2v'+cp] = sum_{s,t in {0,1}}  W[a+2s, b+2t]^T . x[u-s, v-t]

i.e. 4 phases x 4 taps = 16 dense (Cin x Cout) GEMMs over the whole tile —
pure MXU work, zero inserted zeros, and the paper's crop (padding=1) is
folded into the phase/index arithmetic instead of a separate layer.

Tiling: grid (B, H/tile_h); each step loads its row-tile plus the
previous/next tiles (for the one-row halo each side) and writes a
(2*tile_h, 2W) output tile. Channels stay whole (Cin/Cout are the GEMM
dims — pad to 128 lanes upstream for full MXU utilization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import backend


def _phase_matmuls(x_m1, x_0, x_p1, w, th, W):
    """All four parity phases for a row tile.

    x_m1/x_0/x_p1: (th, W, Cin) rows shifted -1/0/+1; w: (4,4,Cin,Cout)
    already rot180-flipped (``w[::-1, ::-1]``, the kernel conv_transpose
    applies — Mosaic has no reverse, so callers flip outside the kernel).
    Returns the four (th, W, Cout) phases ph00, ph01, ph10, ph11 (row
    parity, column parity).
    """
    cin = x_0.shape[-1]
    cout = w.shape[-1]

    def shift_left(v):  # col v'+1
        if W == 1:
            return jnp.zeros_like(v)
        return jnp.concatenate([v[:, 1:], jnp.zeros_like(v[:, :1])], axis=1)

    def shift_right(v):  # col v'-1
        if W == 1:
            return jnp.zeros_like(v)
        return jnp.concatenate([jnp.zeros_like(v[:, :1]), v[:, :-1]], axis=1)

    def mm(xs, ki, kj):
        flat = xs.reshape(th * W, cin)
        return jax.lax.dot_general(
            flat,
            w[ki, kj],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(th, W, cout)

    # row parity 0 (even output rows): uses x rows u (W[1,:]) and u-1 (W[3,:])
    # row parity 1 (odd):              uses x rows u+1 (W[0,:]) and u (W[2,:])
    ph00 = mm(x_0, 1, 1) + mm(shift_right(x_0), 1, 3) + mm(x_m1, 3, 1) + mm(shift_right(x_m1), 3, 3)
    ph01 = mm(shift_left(x_0), 1, 0) + mm(x_0, 1, 2) + mm(shift_left(x_m1), 3, 0) + mm(x_m1, 3, 2)
    ph10 = mm(x_p1, 0, 1) + mm(shift_right(x_p1), 0, 3) + mm(x_0, 2, 1) + mm(shift_right(x_0), 2, 3)
    ph11 = mm(shift_left(x_p1), 0, 0) + mm(x_p1, 0, 2) + mm(shift_left(x_0), 2, 0) + mm(x_0, 2, 2)
    return ph00, ph01, ph10, ph11


def _interleave(ph00, ph01, ph10, ph11):
    """Four (th, W, C) parity phases -> the (th, 2, W, 2, C) output tile."""
    even = jnp.stack([ph00, ph01], axis=2)  # (th, W, 2, Cout)
    odd = jnp.stack([ph10, ph11], axis=2)
    return jnp.stack([even, odd], axis=1)  # (th, 2, W, 2, Cout)


def _deconv_kernel(x_prev_ref, x_ref, x_next_ref, w_ref, o_ref, *, th, W, n_tiles):
    i = pl.program_id(1)
    x_0 = x_ref[0]  # (th, W, Cin)
    # row u-1: last row of the previous tile on top; masked at global top
    prev_last = x_prev_ref[0, th - 1 : th]
    prev_last = jnp.where(i > 0, prev_last, jnp.zeros_like(prev_last))
    x_m1 = jnp.concatenate([prev_last, x_0[:-1]], axis=0)
    # row u+1: first row of the next tile at the bottom; masked at bottom
    next_first = x_next_ref[0, 0:1]
    next_first = jnp.where(i < n_tiles - 1, next_first, jnp.zeros_like(next_first))
    x_p1 = jnp.concatenate([x_0[1:], next_first], axis=0)

    tile = _interleave(*_phase_matmuls(x_m1, x_0, x_p1, w_ref[...], th, W))
    o_ref[0] = tile.reshape(2 * th, 2 * W, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_h",))
def deconv2d_pallas(x, w, tile_h: int = 8):
    """Stride-2, k=4, torch-padding-1 transposed conv (the Pix2Pix up-op).

    x: (B, H, W, Cin) -> (B, 2H, 2W, Cout). Weights (4, 4, Cin, Cout).
    """
    B, H, W, Cin = x.shape
    assert w.shape[:2] == (4, 4), "phase decomposition is specialized to k=4"
    Cout = w.shape[-1]
    if H % tile_h:
        tile_h = H  # small inputs: single tile
    n_tiles = H // tile_h

    grid = (B, n_tiles)
    kernel = functools.partial(_deconv_kernel, th=tile_h, W=W, n_tiles=n_tiles)
    def x_spec(off):
        def imap(b, i):
            return (b, jnp.clip(i + off, 0, n_tiles - 1), 0, 0)

        return pl.BlockSpec((1, tile_h, W, Cin), imap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            x_spec(-1),
            x_spec(0),
            x_spec(+1),
            pl.BlockSpec((4, 4, Cin, Cout), lambda b, i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2 * tile_h, 2 * W, Cout), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 2 * H, 2 * W, Cout), x.dtype),
        compiler_params=backend.compiler_params(2),
        interpret=backend.interpret(),
    )(x, x, x, w[::-1, ::-1])
