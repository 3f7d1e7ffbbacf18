"""Jit wrapper: flash attention with XLA fallback for odd shapes."""
from __future__ import annotations

from .kernel import flash_attention
from .ref import attention_ref


def attention(q, k, v, causal=True, window=0, softcap=None, scale=None, use_pallas=True):
    Sq, Sk, D = q.shape[1], k.shape[1], q.shape[-1]
    blockable = Sq % min(128, Sq) == 0 and Sk % min(128, Sk) == 0
    if use_pallas and blockable and q.shape[2] % k.shape[2] == 0:
        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    return attention_ref(q, k, v, causal=causal, window=window, softcap=softcap, scale=scale)
