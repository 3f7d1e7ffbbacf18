"""Fused serving kernels + implementation-aware planning.

Parity: each fused Pallas block (conv+norm+act, deconv+crop+norm+act)
matches its pure-jnp oracle on serving shapes at f32/bf16. Planning: the
``--impl auto`` argmin is never analytically worse than forced ``xla``
on both serving graphs, the measured-cost plan binds ``pallas_fused``
segments that survive the PlanIR JSON round trip, and the executor
stages the fused variants end-to-end bit-compatibly with ``run_all``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
from repro.core.cost_model import ANALYTIC, MeasuredCost
from repro.core.engine import jetson_orin_engines
from repro.core.scheduler import _nmodel_schedule_impl as nmodel_schedule
from repro.kernels.fused.ops import conv_block, deconv_block
from repro.kernels.fused.ref import conv_block_ref, deconv_block_ref
from repro.models import Pix2PixConfig, Pix2PixGenerator, YOLOv8, YOLOv8Config


# (in_shape, kernel, stride, padding, cout, norm, act) — the serving-graph
# blocks the fused kernels replace (Pix2Pix down/up path, YOLO convs)
CONV_CASES = [
    ((1, 64, 64, 3), 4, 2, 1, 8, "none", "lrelu"),
    ((1, 32, 32, 8), 4, 2, 1, 16, "batch", "lrelu"),
    ((1, 64, 64, 3), 3, 2, 1, 16, "batch", "silu"),
    ((1, 32, 32, 16), 3, 2, 1, 32, "batch", "silu"),
    ((2, 16, 16, 8), 4, 2, 1, 16, "instance", "lrelu"),  # B>1 per-sample stats
    ((1, 16, 16, 8), 4, 2, 1, 16, "group", "lrelu"),
    ((1, 16, 16, 16), 3, 1, 1, 16, "batch", "silu"),  # stride 1, no space-to-depth
    ((1, 8, 8, 32), 1, 1, 0, 256, "batch", "silu"),  # two 128-lane channel tiles
]
DECONV_CASES = [
    ((1, 4, 4, 64), 32, "batch", "relu"),
    ((1, 8, 8, 64), 16, "batch", "relu"),
    ((2, 8, 8, 16), 8, "instance", "relu"),
    ((2, 8, 8, 16), 8, "batch", "relu"),  # B>1: statistics over the batch
]


def _params(key, cin, cout, k):
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (k, k, cin, cout), jnp.float32) * 0.1
    b = jax.random.normal(kb, (cout,), jnp.float32) * 0.1
    gamma = jnp.ones((cout,), jnp.float32) * 1.1
    beta = jnp.zeros((cout,), jnp.float32) + 0.05
    return w, b, gamma, beta


@pytest.mark.parametrize("shape,k,stride,pad,cout,norm,act", CONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv_block_parity(shape, k, stride, pad, cout, norm, act, dtype):
    x = jax.random.normal(jax.random.key(0), shape).astype(dtype)
    w, b, gamma, beta = _params(jax.random.key(1), shape[-1], cout, k)
    groups = 4 if norm == "group" else 1
    got = conv_block(
        x, w, b, gamma, beta, stride=stride, padding=pad, norm=norm, groups=groups, act=act
    )
    want = conv_block_ref(
        x, w, b, gamma, beta, stride=stride, padding=pad, norm=norm, groups=groups, act=act
    )
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.float32(got), np.float32(want), atol=atol)


@pytest.mark.parametrize("shape,cout,norm,act", DECONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_deconv_block_parity(shape, cout, norm, act, dtype):
    x = jax.random.normal(jax.random.key(0), shape).astype(dtype)
    w, b, gamma, beta = _params(jax.random.key(1), shape[-1], cout, 4)
    got = deconv_block(x, w, b, gamma, beta, norm=norm, act=act)
    want = deconv_block_ref(x, w, b, gamma, beta, norm=norm, act=act)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.float32(got), np.float32(want), atol=atol)


SPPF_CASES = [
    ((1, 8, 8, 16), 5, 3),  # the YOLO SPPF pyramid at serving scale
    ((2, 4, 4, 8), 5, 3),  # B>1: max/concat have no cross-sample coupling
    ((1, 8, 8, 4), 3, 2),
]


@pytest.mark.parametrize("shape,window,reps", SPPF_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sppf_pyramid_parity_exact(shape, window, reps, dtype):
    """The fused SPPF pool-pyramid is max/concat only — bit-exact vs the
    reduce_window oracle at BOTH dtypes, not merely close."""
    from repro.kernels.fused.ops import sppf_pyramid
    from repro.kernels.fused.ref import sppf_pyramid_ref

    x = jax.random.normal(jax.random.key(0), shape).astype(dtype)
    got = sppf_pyramid(x, window=window, reps=reps)
    want = sppf_pyramid_ref(x, window=window, reps=reps)
    assert got.shape == shape[:-1] + ((reps + 1) * shape[-1],)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.float32(got), np.float32(want))


def test_yolo_fine_granularity_pins_sppf_variant_group():
    """At fine granularity the three SPPF pools form the one multi-op
    variant group (they substitute atomically as the fused pyramid);
    every other op keeps per-op substitution."""
    from repro.core.pipeline import yolo_staged
    from repro.models import YOLOv8

    ycfg = YOLOv8Config(img_size=32)
    sm = yolo_staged(ycfg, YOLOv8(ycfg).init(jax.random.key(0)), granularity="fine")
    multi = [(a, b) for a, b in sm.variant_groups if b - a > 1]
    assert len(multi) == 1
    a, b = multi[0]
    names = [sm.ops[i][0] for i in range(a, b)]
    assert names == ["sppf.pool1", "sppf.pool2", "sppf.pool3"]
    # single-op groups cover everything else exactly once
    covered = sorted(i for lo, hi in sm.variant_groups for i in range(lo, hi))
    assert covered == list(range(len(sm.ops)))


def test_conv_block_batchnorm_b2_matches_ref():
    # B>1 batch norm takes cross-sample statistics: the kernel holds the
    # whole batch in one grid step and normalises over it
    x = jax.random.normal(jax.random.key(0), (2, 16, 16, 8))
    w, b, gamma, beta = _params(jax.random.key(1), 8, 16, 4)
    got = conv_block(x, w, b, gamma, beta, stride=2, padding=1, norm="batch", act="lrelu")
    want = conv_block_ref(x, w, b, gamma, beta, stride=2, padding=1, norm="batch", act="lrelu")
    np.testing.assert_allclose(np.float32(got), np.float32(want), atol=1e-5)


# ---------------------------------------------------------------- planning


@pytest.fixture(scope="module")
def serving_graphs():
    g_pix = Pix2PixGenerator(
        Pix2PixConfig(img_size=64, base=8, deconv_mode="cropping")
    ).layer_graph()
    g_yolo = YOLOv8(YOLOv8Config(img_size=64)).layer_graph()
    return [g_pix, g_yolo]


@pytest.fixture(scope="module")
def engines():
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    return [dla, gpu]


@pytest.mark.parametrize("provider", [ANALYTIC, MeasuredCost()], ids=["analytic", "measured"])
def test_auto_never_worse_than_xla_on_serving_pair(serving_graphs, engines, provider):
    p_xla = nmodel_schedule(serving_graphs, engines, provider=provider, impl="xla")
    p_auto = nmodel_schedule(serving_graphs, engines, provider=provider, impl="auto")
    assert p_auto.cycle_time <= p_xla.cycle_time * (1 + 1e-9)


@pytest.mark.parametrize("gi", [0, 1], ids=["pix2pix", "yolov8"])
def test_auto_never_worse_per_graph(serving_graphs, engines, gi):
    # the pin the CI gate rides on: per serving graph, impl-aware planning
    # never loses to forced xla (auto only switches a segment when the
    # fused candidate dominates component-wise)
    g = [serving_graphs[gi]]
    p_xla = nmodel_schedule(g, engines, impl="xla")
    p_auto = nmodel_schedule(g, engines, impl="auto")
    assert p_auto.cycle_time <= p_xla.cycle_time * (1 + 1e-9)


def test_measured_auto_binds_pallas_segments(serving_graphs, engines):
    plan = nmodel_schedule(serving_graphs, engines, provider=MeasuredCost(), impl="auto")
    ir = plan.ir
    assert ir.impl_mode == "auto"
    bindings = ir.impl_bindings()
    assert any(i == "pallas_fused" for b in bindings for i in b), bindings
    assert "pallas_fused" in ir.describe()


def test_default_plan_is_pure_xla(serving_graphs, engines):
    plan = nmodel_schedule(serving_graphs, engines)
    ir = plan.ir
    assert ir.impl_mode == "xla"
    assert all(i == "xla" for b in ir.impl_bindings() for i in b)
    assert "pallas" not in ir.describe()


def test_plan_ir_json_roundtrip_preserves_impl(serving_graphs, engines):
    from repro.core.plan_ir import PlanIR

    plan = nmodel_schedule(serving_graphs, engines, provider=MeasuredCost(), impl="auto")
    rt = PlanIR.from_json(plan.ir.to_json())
    assert rt.impl_mode == plan.ir.impl_mode
    assert rt.impl_bindings() == plan.ir.impl_bindings()


def test_plan_api_validates_impl(serving_graphs, engines):
    from repro.core import api

    with pytest.raises(ValueError):
        api.plan(serving_graphs, engines, impl="fused")


def test_measured_coverage_reports_both_impls(serving_graphs):
    mc = MeasuredCost()
    for g in serving_graphs:
        rep = mc.coverage_report(g)
        assert set(rep) == {"xla", "pallas_fused"}
        assert rep["pallas_fused"]["coverage"] > 0.5


# ---------------------------------------------------------------- execution


def test_server_executes_pallas_plan_matches_run_all():
    from repro.serve import MultiStreamServer, build_pix_yolo_serving, merge_flags_for

    models, plan, streams, _ = build_pix_yolo_serving(
        img=32, base=8, n_pix=1, n_yolo=1, impl="pallas"
    )
    assert any(i == "pallas_fused" for b in plan.ir.impl_bindings() for i in b)
    server = MultiStreamServer(
        models,
        plan,
        streams,
        max_queue=4,
        microbatch=1,
        merge_batches=merge_flags_for(models),
        dispatch="overlapped",
        jit_segments=True,
    )
    x = jax.random.normal(jax.random.key(0), (1, 32, 32, 3))
    for s in streams:
        server.submit(s.model_index, x)
    server.pump()
    outs = server.drain()
    for s, model in zip(streams, models):
        ref = model.run_all(x)
        for got in outs[s.name]:
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
                np.testing.assert_allclose(
                    np.float32(a), np.float32(b), atol=5e-3, rtol=1e-2
                )


# ------------------------------------------------- bare 1x1 head convs


def test_yolo_head_bare_convs_register_span1_fuse_groups():
    """The YOLO head's final box3/cls3 convs (conv+bias, no norm/act)
    carry span-1 ``pallas_fused`` fuse attrs on the expanded graph —
    one fused kernel per conv, exact at any batch (no batch-norm
    caveat)."""
    g = YOLOv8(YOLOv8Config(img_size=32)).layer_graph().expand()
    heads = {
        l.name: l.attrs["fuse"]
        for l in g
        if (l.name.endswith(".box3") or l.name.endswith(".cls3")) and "fuse" in l.attrs
    }
    # every detection scale registers both head convs
    assert {n.split(".")[0] for n in heads} == {"head3", "head4", "head5"}
    assert len(heads) == 6
    for fu in heads.values():
        assert fu["span"] == 1
        assert (fu["kind"], fu["norm"], fu["act"]) == ("conv", "none", "none")
        assert fu["flops"] > 0 and fu["bytes"] > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_yolo_head_conv_fused_parity(dtype):
    """The fused norm-free/act-free conv_block matches the plain Conv2D
    head conv at both serving dtypes on the real head shapes."""
    from repro.nn.conv import Conv2D

    for i, (shape, cout) in enumerate(
        [((1, 4, 4, 64), 64), ((1, 2, 2, 128), 2), ((1, 1, 1, 256), 64)]
    ):
        cin = shape[-1]
        x = jax.random.normal(jax.random.key(2 * i), shape).astype(dtype)
        w = (jax.random.normal(jax.random.key(2 * i + 1), (1, 1, cin, cout)) * 0.1).astype(
            jnp.float32
        )
        b = (jax.random.normal(jax.random.key(100 + i), (cout,)) * 0.1).astype(jnp.float32)
        got = conv_block(x, w, b=b, stride=1, padding=0, norm="none", act="none")
        want = Conv2D(cin, cout, 1, 1, padding=0)({"w": w, "b": b}, x)
        assert got.dtype == want.dtype
        atol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.float32(got), np.float32(want), atol=atol)
