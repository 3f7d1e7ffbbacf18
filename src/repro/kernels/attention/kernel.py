"""Pallas TPU flash attention (forward).

Block-wise online softmax: grid (B, Hq, Sq/bq); each step streams the KV
sequence in ``bk``-sized VMEM blocks, keeping running (max, sum, acc) in
registers. GQA maps query head h to KV head h // (Hq//Hk) in the BlockSpec
index map (no KV replication in HBM). Causal + sliding-window blocks are
*skipped*, not masked — the sparsity becomes wall-clock, which is exactly
the gemma2 local-layer win. Logit softcap (gemma2) applied in-block.

VMEM budget per step: q (bq, D) + k/v (bk, D) each + acc (bq, D) fp32 —
with bq=bk=512, D=256: ~1.8 MB, comfortably inside the ~16 MB VMEM.
MXU alignment: choose bq/bk multiples of 128 and D in {64,128,256}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import backend

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, Sk, causal, window, softcap, scale, q_offset):
    qi = pl.program_id(2)
    q = q_ref[0, :, 0, :].astype(jnp.float32)  # (bq, D)
    D = q.shape[-1]

    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq) + q_offset  # global key-aligned positions

    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, D), jnp.float32)

    n_kb = Sk // bk
    # block range: causal => kv blocks beyond the last query are skipped;
    # window => kv blocks older than (min q_pos - window) are skipped.
    hi = n_kb if not causal else jnp.minimum(n_kb, (qi * bq + bq - 1 + q_offset) // bk + 1)
    lo = 0
    if window and window > 0:
        lo = jnp.maximum(0, (qi * bq + q_offset - window + 1) // bk)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * bk, bk), 0, :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * bk, bk), 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = kb * bk + jax.lax.iota(jnp.int32, bk)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window and window > 0:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m, l, acc))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale", "bq", "bk")
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    window: int = 0,
    softcap: float | None = None,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hk, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    G = Hq // Hk
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    scale_v = scale if scale is not None else float(1.0 / D**0.5)
    q_offset = Sk - Sq  # align query block positions with absolute key ids

    kernel = functools.partial(
        _flash_kernel,
        bq=bq,
        bk=bk,
        Sk=Sk,
        causal=causal,
        window=window,
        softcap=softcap,
        scale=scale_v,
        q_offset=q_offset,
    )
    grid = (B, Hq, Sq // bq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, i: (b, i, h, 0)),
            pl.BlockSpec((1, Sk, 1, D), lambda b, h, i: (b, 0, h // G, 0)),
            pl.BlockSpec((1, Sk, 1, D), lambda b, h, i: (b, 0, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, D), lambda b, h, i: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, Hq, D), q.dtype),
        compiler_params=backend.compiler_params(3),
        interpret=backend.interpret(),
    )(q, k, v)
