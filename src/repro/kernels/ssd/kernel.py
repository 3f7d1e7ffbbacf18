"""Pallas TPU kernel: Mamba-2 SSD chunked scan (forward).

Grid (B, H, S/chunk) with the chunk axis 'arbitrary' (sequential): the
inter-chunk SSM state (P, N) lives in a VMEM scratch ref that persists
across grid steps — the standard Pallas-TPU carry idiom. Per chunk the
work is dense MXU matmuls (CB^T scores, masked-decay apply, state
update), i.e. the SSD duality's matmul-rich form; nothing is recurrent at
the element level, matching how the original Triton kernel restructures
the scan for tensor cores — re-expressed here for MXU tiles.

B/C are per-group: the BlockSpec index map sends head h to group
h // (H/G), so grouped B/C are never materialized per-head in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *, chunk, P, N):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros((P, N), jnp.float32)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (L,)
    a = a_ref[0].astype(jnp.float32)  # scalar (per head)
    bmat = b_ref[0, :, 0, :].astype(jnp.float32)  # (L, N)
    cmat = c_ref[0, :, 0, :].astype(jnp.float32)  # (L, N)

    dA = dt * a  # (L,)
    dA_cum = jnp.cumsum(dA)  # (L,)

    # intra-chunk: scores (L, L) = C B^T ⊙ decay(L), lower-triangular
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    seg = dA_cum[:, None] - dA_cum[None, :]  # decay from j..i (i >= j)
    li = jax.lax.iota(jnp.int32, chunk)
    causal = li[:, None] >= li[None, :]
    L = jnp.where(causal, jnp.exp(seg), 0.0)
    xw = x * dt[:, None]  # dt-weighted inputs
    y_intra = jax.lax.dot_general(scores * L, xw, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # inter-chunk: y += (C . h_prev) * exp(dA_cum)
    h_prev = state_ref[...]  # (P, N)
    y_inter = jax.lax.dot_general(cmat, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(dA_cum)[:, None]

    o_ref[0, :, 0, :] = (y_intra + y_inter).astype(o_ref.dtype)

    # state update: h = h * exp(sum dA) + sum_l exp(dA_cum[-1]-dA_cum[l]) dt_l x_l B_l^T
    decay_states = jnp.exp(dA_cum[-1] - dA_cum)  # (L,)
    xw_dec = xw * decay_states[:, None]  # (L, P)
    delta = jax.lax.dot_general(xw_dec, bmat, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_ref[...] = h_prev * jnp.exp(dA_cum[-1]) + delta


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_pallas(x, dt, A, B, C, chunk: int = 128):
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B/C: (b, s, g, n) -> y like x."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    rep = h // g
    grid = (b, h, s // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, P=p, N=n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, 1, n), lambda bi, hi, ci: (bi, ci, hi // rep, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda bi, hi, ci: (bi, ci, hi // rep, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=backend.VMEM_LIMIT_BYTES,
        ),
        interpret=backend.interpret(),
    )(x, dt, A, B, C)
