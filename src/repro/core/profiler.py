"""Per-layer dry-run profiler — the trtexec analogue.

Re-derives ``LayerMeta.flops`` / ``bytes_accessed`` from XLA's
``compiled.cost_analysis()`` by lowering each compute layer individually
on ShapeDtypeStructs (no allocation). The scheduler can then run against
*compiler-measured* costs instead of analytic estimates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .graph import LayerGraph


def _elementwise_fn(kind: str):
    """Representative lowering per non-conv layer kind. All of these are
    memory-bound elementwise/shuffle ops, so one op per kind is enough for
    XLA's byte/flop accounting to replace the analytic estimate."""
    if kind in ("act",):
        return lambda x: jax.nn.leaky_relu(x, 0.2)
    if kind in ("tanh",):
        return jnp.tanh
    if kind in ("bn", "norm"):
        # inference-time normalization is a per-channel affine
        def bn(x):
            g = jnp.ones((x.shape[-1],), x.dtype)
            b = jnp.zeros((x.shape[-1],), x.dtype)
            return x * g + b

        return bn
    if kind == "concat":
        # the graph meta's shape is the concatenated result; lower the
        # concat of its two halves along the channel axis
        def cat(x):
            h = x.shape[-1] // 2
            return jnp.concatenate([x[..., :h], x[..., h or 1 :]], axis=-1)

        return cat
    if kind in ("crop", "pad"):
        def crop(x):
            if x.ndim >= 3 and x.shape[1] > 2 and x.shape[2] > 2:
                return x[:, 1:-1, 1:-1, ...]
            return x * jnp.asarray(1.0, x.dtype)

        return crop
    if kind == "pool":
        def pool(x):
            return jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 1, 1, 1), "SAME"
            )

        return pool
    if kind == "dropout":
        return lambda x: x * jnp.asarray(1.0, x.dtype)  # inference passthrough
    if kind == "add":
        return lambda x: x + x  # residual merge (two reads, one write)
    return None


ELEMENTWISE_KINDS = ("act", "tanh", "bn", "norm", "concat", "crop", "pad", "pool", "dropout", "add")


@functools.lru_cache(maxsize=2048)
def _elementwise_cost(kind, in_shape, dtype_str):
    """XLA-measured (flops, bytes) for one elementwise-ish layer. Returns
    transcendentals folded into flops (tanh etc. count there)."""
    fn = _elementwise_fn(kind)
    if fn is None:
        return 0.0, 0.0
    x = jax.ShapeDtypeStruct(tuple(in_shape), jnp.dtype(dtype_str))
    compiled = jax.jit(fn).lower(x).compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0)) + float(ca.get("transcendentals", 0.0))
    return flops, float(ca.get("bytes accessed", 0.0))


@functools.lru_cache(maxsize=512)
def _conv_cost(in_shape, kernel, stride, padding, c_out, transposed, dtype_str):
    dtype = jnp.dtype(dtype_str)
    x = jax.ShapeDtypeStruct(in_shape, dtype)
    w = jax.ShapeDtypeStruct((kernel, kernel, in_shape[-1], c_out), dtype)

    if transposed:

        def f(x, w):
            y = jax.lax.conv_transpose(
                x, w, strides=(stride, stride), padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
            )
            if padding:
                y = y[:, padding:-padding, padding:-padding, :]
            return y

    else:

        def f(x, w):
            pad = [(padding, padding), (padding, padding)] if padding else "VALID"
            return jax.lax.conv_general_dilated(
                x, w, (stride, stride), pad, dimension_numbers=("NHWC", "HWIO", "NHWC")
            )

    compiled = jax.jit(f).lower(x, w).compile()
    ca = compiled.cost_analysis()
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


@functools.lru_cache(maxsize=512)
def _fused_cost(in_shape, kernel, stride, padding, c_out, transposed, norm, act, dtype_str):
    """XLA-measured (flops, bytes) for one fused conv/deconv+norm+act
    block lowered as a SINGLE jit region: the compiler fuses the epilogue,
    so ``bytes accessed`` counts the block's input, output, and params
    once — the honest cost of the Pallas fused kernel, directly comparable
    against the sum of the per-layer ``_conv_cost``/``_elementwise_cost``
    lowerings the xla implementation pays (which round-trip every
    intermediate through HBM)."""
    dtype = jnp.dtype(dtype_str)
    x = jax.ShapeDtypeStruct(in_shape, dtype)
    w = jax.ShapeDtypeStruct((kernel, kernel, in_shape[-1], c_out), dtype)
    v = jax.ShapeDtypeStruct((c_out,), jnp.float32)

    def f(x, w, gamma, beta):
        if transposed:
            y = jax.lax.conv_transpose(
                x, w, strides=(stride, stride), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            if padding:
                y = y[:, padding:-padding, padding:-padding, :]
        else:
            pad = [(padding, padding), (padding, padding)] if padding else "VALID"
            y = jax.lax.conv_general_dilated(
                x, w, (stride, stride), pad, dimension_numbers=("NHWC", "HWIO", "NHWC")
            )
        y = y.astype(jnp.float32)
        if norm != "none":
            # inference-time normalization is a per-channel affine (same
            # stand-in _elementwise_cost uses for the unfused bn layer)
            y = y * gamma + beta
        if act == "relu":
            y = jax.nn.relu(y)
        elif act == "lrelu":
            y = jax.nn.leaky_relu(y, 0.2)
        elif act == "silu":
            y = jax.nn.silu(y)
        elif act == "tanh":
            y = jnp.tanh(y)
        return y.astype(dtype)

    compiled = jax.jit(f).lower(x, w, v, v).compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0)) + float(ca.get("transcendentals", 0.0))
    return flops, float(ca.get("bytes accessed", 0.0))


@functools.lru_cache(maxsize=128)
def _sppf_cost(in_shape, window, reps, dtype_str):
    """XLA-measured (flops, bytes) for the SPPF pool pyramid + concat
    lowered as a SINGLE jit region: ``reps`` cascaded stride-1 max pools
    whose intermediates feed both the next pool and the final concat.
    Fused, the input is read once and only the 4C concat is written —
    the honest cost of the Pallas ``sppf_pyramid`` kernel, comparable
    against the sum of the per-pool ``_elementwise_cost`` lowerings the
    xla implementation pays."""
    dtype = jnp.dtype(dtype_str)
    x = jax.ShapeDtypeStruct(tuple(in_shape), dtype)
    pad = window // 2

    def f(x):
        outs = [x]
        for _ in range(reps):
            outs.append(
                jax.lax.reduce_window(
                    outs[-1],
                    -jnp.inf,
                    jax.lax.max,
                    (1, window, window, 1),
                    (1, 1, 1, 1),
                    [(0, 0), (pad, pad), (pad, pad), (0, 0)],
                )
            )
        return jnp.concatenate(outs, axis=-1)

    compiled = jax.jit(f).lower(x).compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0)) + float(ca.get("transcendentals", 0.0))
    return flops, float(ca.get("bytes accessed", 0.0))


def _profile_layer(l, dtype_name: str):
    """Measured clone of one meta. Composites are profiled through their
    primitive decomposition and their totals become the measured sums, so
    profiling a coarse hierarchical graph and profiling its expansion
    agree layer-for-layer."""
    if l.sublayers:
        subs = [_profile_layer(p, dtype_name) for p in l.sublayers]
        return l.clone(
            sublayers=subs,
            flops=sum(p.flops for p in subs),
            bytes_accessed=sum(p.bytes_accessed for p in subs),
        )
    if l.kind in ("conv", "deconv"):
        flops, bytes_ = _conv_cost(
            tuple(l.in_shape),
            l.attrs.get("kernel", 1),
            l.attrs.get("stride", 1),
            l.attrs.get("padding", 0),
            l.out_shape[-1],
            l.kind == "deconv",
            dtype_name,
        )
        return l.clone(flops=flops or l.flops, bytes_accessed=bytes_ or l.bytes_accessed)
    if l.kind in ELEMENTWISE_KINDS:
        flops, bytes_ = _elementwise_cost(l.kind, tuple(l.in_shape), dtype_name)
        return l.clone(flops=flops or l.flops, bytes_accessed=bytes_ or l.bytes_accessed)
    return l.clone()


def profile_graph(graph: LayerGraph, dtype=jnp.bfloat16) -> LayerGraph:
    """Return a copy of ``graph`` with XLA-measured flops/bytes on conv,
    deconv, and elementwise (pointwise/norm/concat/...) layers; composite
    kinds (c2f, sppf, head, ...) are measured through their primitive
    decomposition (undecomposed composites keep analytic estimates).
    Works on coarse and expanded graphs alike."""
    name = jnp.dtype(dtype).name
    out = [_profile_layer(l, name) for l in graph]
    return LayerGraph(graph.model_name + "[profiled]", out).renumber()
