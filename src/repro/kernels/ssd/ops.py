"""Jit wrapper: Pallas SSD with jnp fallback for non-chunk-multiple seqs."""
from __future__ import annotations

from .kernel import ssd_pallas
from .ref import ssd_ref


def ssd(x, dt, A, B, C, chunk: int = 128, use_pallas: bool = True):
    s = x.shape[1]
    if use_pallas and s % min(chunk, s) == 0:
        return ssd_pallas(x, dt, A, B, C, chunk=min(chunk, s))
    return ssd_ref(x, dt, A, B, C, chunk=chunk)
