"""Compile the serving path's Pallas kernels for a TPU v5e at 256^2.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: these tests lower every fused block the
``impl="pallas"`` plan stages at published size (Pix2Pix cropping, base
64; YOLOv8n) with ``interpret=False`` and check that Mosaic accepts it —
what interpret mode cannot show (tile alignment, VMEM limits, lowerable
ops). One full-width jitted Pix2Pix segment compiles as well.

The topology is described inside a fixture (one process may load the TPU
library at a time, so never at import), and JAX's persistent compilation
cache is off around these compiles: a compile for a described chip is
written to the cache but cannot be read back without one.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import pix2pix_mri, yolov8_stroke
from repro.kernels import backend
from repro.kernels.fused.kernel import conv_block_pallas, deconv_block_pallas, sppf_pyramid_pallas
from repro.models import Pix2PixGenerator, YOLOv8

PIX = dataclasses.replace(pix2pix_mri.CONFIG_CROPPING, base=64)


def _fused_blocks():
    """One entry per distinct fused-kernel call of the two serving graphs
    at 256^2: (id, kind, input shape, static args)."""
    graphs = {
        "pix2pix": Pix2PixGenerator(PIX).layer_graph(),
        "yolov8": YOLOv8(yolov8_stroke.CONFIG).layer_graph().expand(),
    }
    seen = {}
    for model, g in graphs.items():
        for l in g:
            fu = l.attrs.get("fuse")
            if fu is None:
                continue
            if fu["kind"] == "pool":
                key = ("pool", l.in_shape, ())
            elif fu["kind"] == "deconv":
                key = ("deconv", l.in_shape, (l.out_shape[-1], fu["norm"], fu["act"]))
            else:
                a = l.attrs
                key = ("conv", l.in_shape,
                       (l.out_shape[-1], a["kernel"], a["stride"], a["padding"], fu["norm"], fu["act"]))
            seen.setdefault(key, f"{model}.{l.name}")
    return [pytest.param(*key, id=name) for key, name in seen.items()]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or it cannot be loaded
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(one_chip, monkeypatch):
    """Kernels compiled, not interpreted, with the persistent cache off;
    yields a ShapeDtypeStruct factory on the described chip."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(backend, "interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # no trace made in interpret mode may be reused
    try:
        yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,shape,static", _fused_blocks())
def test_fused_block_compiles_for_v5e(mosaic, kind, shape, static):
    x = mosaic(shape)
    if kind == "pool":
        compiled = jax.jit(sppf_pyramid_pallas).lower(x).compile()
    else:
        if kind == "deconv":
            cout, norm, act = static
            k, fn = 4, lambda *a: deconv_block_pallas(*a, norm=norm, act=act)
        else:
            cout, k, stride, padding, norm, act = static
            fn = lambda *a: conv_block_pallas(*a, stride=stride, padding=padding, norm=norm, act=act)
        vec = mosaic((cout,))
        compiled = jax.jit(fn).lower(x, mosaic((k, k, shape[-1], cout)), vec, vec, vec).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pix2pix_encoder_segment_compiles_for_v5e(mosaic):
    """The whole fused encoder (down0..down7) as one jitted serving
    segment, params and frame at published width."""
    from repro.core.pipeline import pix2pix_staged

    gen = Pix2PixGenerator(PIX)
    params = jax.eval_shape(gen.init, jax.random.key(0))
    model = pix2pix_staged(PIX, params)
    hi = next(i for i, l in enumerate(model.graph) if l.name.startswith("up0."))
    fn = model.jitted_segment_fn(0, hi, impl="pallas_fused")
    state = {"x": mosaic((1, 256, 256, 3)), "skips": []}
    on_chip = jax.tree.map(lambda s: mosaic(s.shape, s.dtype), params)
    compiled = fn.lower(on_chip, state).compile()
    assert compiled.as_text().count("tpu_custom_call") == 8  # one per down block
