"""Consistency tests for the analytic FLOP/byte models that power the
roofline: on single-level-scan programs XLA's HLO flop count is trustworthy
(verified earlier); the analytic model must agree there."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, get_arch
from repro.launch.analytic import analytic_bytes, analytic_flops, fwd_flops_per_token


def test_analytic_vs_hlo_forward_smoke():
    """Small LM forward: analytic fwd flops within 20% of XLA's count."""
    spec = get_arch("phi4_mini_3_8b")
    cfg = dataclasses.replace(
        spec.smoke, n_layers=2, vocab=2048, attn_chunk=0, remat=False, act_dtype=jnp.float32
    )
    from repro.configs import build_model

    model = build_model(cfg)
    B, S = 2, 256
    ab = model.abstract(jnp.float32)
    c = (
        jax.jit(lambda p, t: model(p, t))
        .lower(ab, jax.ShapeDtypeStruct((B, S), jnp.int32))
        .compile()
    )

    hlo = float(c.cost_analysis()["flops"])
    analytic = B * S * fwd_flops_per_token(cfg, S, "train")
    # the analytic model counts causal-HALF attention (what a flash kernel
    # executes); XLA's dense-masked path does the full S^2 — so analytic may
    # sit up to ~30% above HLO at tiny scale where attention dominates.
    assert abs(hlo - analytic) / hlo < 0.35, (hlo, analytic)


def test_analytic_flops_scaling_relations():
    spec = get_arch("gemma2_2b")
    train = analytic_flops(spec, "train_4k")
    prefill = analytic_flops(spec, "prefill_32k")
    decode = analytic_flops(spec, "decode_32k")
    # train executes fwd+bwd+remat on 1M tokens; decode touches B tokens
    assert train > prefill > decode
    # decode flops per token exceed prefill per-token (full-context keys)
    t_pre = prefill / (32 * 32768)
    t_dec = decode / 128
    assert t_dec > t_pre


def test_analytic_bytes_mla_cache_advantage():
    """MLA's compressed KV must show up as lower decode traffic."""
    moe = analytic_bytes(get_arch("deepseek_moe_16b"), "decode_32k", 256)
    mla = analytic_bytes(get_arch("deepseek_v2_lite_16b"), "decode_32k", 256)
    assert mla < moe * 0.6


def test_analytic_bytes_window_advantage():
    """Sliding-window archs read less cache than full attention."""
    g2 = analytic_bytes(get_arch("gemma2_2b"), "decode_32k", 256)  # half local
    g1 = analytic_bytes(get_arch("gemma_2b"), "decode_32k", 256)  # MQA though!
    # gemma-2b has kv=1 (tiny cache); compare gemma2 against itself w/o windows
    spec = get_arch("gemma2_2b")
    full = dataclasses.replace(spec.config, layer_pattern="global")
    spec_full = dataclasses.replace(spec, config=full)
    assert g2 < analytic_bytes(spec_full, "decode_32k", 256)
