"""Pallas TPU kernels: fused conv/deconv + norm + activation serving blocks.

The serving hot path runs `conv -> norm -> act` (Pix2Pix down blocks, every
YOLO fused conv block) and `deconv -> crop -> norm -> act` (Pix2Pix up
blocks) as separate XLA ops: each stage round-trips the activation through
HBM. These kernels fuse a whole block into one pallas_call — the conv is
tap-decomposed into k*k dense (Cin x Cout) GEMMs (pure MXU work, same
idiom as the phase-decomposed deconv), the norm statistics and the
activation are applied in-register, and only the block's final output is
written back.

Grid is (B, Cout / tn): one sample's whole spatial extent per step, and a
128-lane channel tile where Cout divides into them (norm statistics are
per channel, so a channel tile is exact; group norm keeps every channel in
one tile). The input block index does not move along the channel axis, so
each sample is fetched once. Batch norm at B > 1 takes statistics over the
batch, so that case holds the whole batch in one step instead.

Mosaic lowers neither strided value slices nor reversed axes, so the
wrappers do both in XLA before the kernel: a stride-2 conv is padded and
space-to-depth folded into a stride-1 conv over 4*Cin channels (weights
folded to match), and the deconv's rot180 weight flip is applied to the
weights. Both are layout passes over the input or the weights only.

The deconv kernel reuses the phase-matmul decomposition from
``kernels.deconv`` (k=4, stride=2; torch padding=1 — i.e. the paper's
crop — folded into the phase arithmetic, so deconv+crop is one kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import backend
from ..deconv.kernel import _phase_matmuls

ACTS = ("none", "relu", "lrelu", "silu", "tanh")
NORMS = ("none", "batch", "instance", "group")
LANES = 128


def _act(y, act):
    if act == "relu":
        return jax.nn.relu(y)
    if act == "lrelu":
        return jax.nn.leaky_relu(y, 0.2)
    if act == "silu":
        return jax.nn.silu(y)
    if act == "tanh":
        return jnp.tanh(y)
    return y


def _standardize(tiles, *, groups, eps):
    """Zero-mean unit-variance over a list of (H, W, C) fp32 tiles, per
    channel (``groups`` == 1) or per channel group."""
    H, W, C = tiles[0].shape
    if groups > 1:
        tiles = [t.reshape(H, W, groups, C // groups) for t in tiles]
        axes, n = (0, 1, 3), len(tiles) * H * W * (C // groups)
    else:
        axes, n = (0, 1), len(tiles) * H * W
    mean = sum(jnp.sum(t, axis=axes, keepdims=True) for t in tiles) / n
    var = sum(jnp.sum(jnp.square(t - mean), axis=axes, keepdims=True) for t in tiles) / n
    inv = jax.lax.rsqrt(var + eps)
    return [((t - mean) * inv).reshape(H, W, C) for t in tiles]


def _norm_act(samples, gamma, beta, *, norm, groups, act, eps):
    """Norm + activation over ``samples``, each a list of (H, W, C) fp32
    tiles (one conv output, or the four deconv phases). Batch norm takes
    its statistics over every tile of every sample given (the whole
    batch); instance and group norm over each sample's tiles alone."""
    if norm == "batch":
        per = len(samples[0])
        flat = _standardize([t for s in samples for t in s], groups=1, eps=eps)
        samples = [flat[i : i + per] for i in range(0, len(flat), per)]
    elif norm in ("instance", "group"):
        g = groups if norm == "group" else 1
        samples = [_standardize(s, groups=g, eps=eps) for s in samples]
    if norm != "none":
        samples = [[t * gamma + beta for t in s] for s in samples]
    return [[_act(t, act) for t in s] for s in samples]


def _channel_tile(cout: int, norm: str) -> int:
    if norm != "group" and cout > LANES and cout % LANES == 0:
        return LANES
    return cout


def _samples_per_step(batch: int, norm: str) -> int:
    return batch if norm == "batch" else 1


def _vec_spec(tn):
    # per-channel vectors travel as (1, C) rows: Mosaic tiles 1-D operands
    # differently from XLA once C passes one lane tile
    return pl.BlockSpec((1, tn), lambda bi, j: (0, j))


def _rows(*vecs):
    return [v.reshape(1, -1) for v in vecs]


def _conv_block_kernel(
    x_ref, w_ref, b_ref, g_ref, bt_ref, o_ref, *, k, pad, Ho, Wo, norm, groups, act, eps
):
    w = w_ref[...].astype(jnp.float32)  # (k, k, Cin, tn)
    cin, cout = w.shape[2], w.shape[3]
    bias = b_ref[...].astype(jnp.float32)
    ys = []
    for bi in range(x_ref.shape[0]):
        x = x_ref[bi].astype(jnp.float32)
        if pad:
            x = jnp.pad(x, ((pad, pad), (pad, pad), (0, 0)))
        acc = jnp.zeros((Ho * Wo, cout), jnp.float32)
        # tap decomposition: k*k unit-stride windows, each a dense GEMM
        for ki in range(k):
            for kj in range(k):
                win = x[ki : ki + Ho, kj : kj + Wo].reshape(Ho * Wo, cin)
                acc = acc + jnp.dot(win, w[ki, kj], preferred_element_type=jnp.float32)
        ys.append([acc.reshape(Ho, Wo, cout) + bias])
    ys = _norm_act(ys, g_ref[...].astype(jnp.float32), bt_ref[...].astype(jnp.float32),
                   norm=norm, groups=groups, act=act, eps=eps)
    for bi, (y,) in enumerate(ys):
        o_ref[bi] = y.astype(o_ref.dtype)


def _space_to_depth(x, w, padding, Ho, Wo):
    """Fold a stride-2 conv into a stride-1 one: pad, then move each 2x2
    pixel phase into channels. The k x k kernel becomes ceil(k/2) squared
    taps over 4*Cin channels, zero where a folded tap falls past k."""
    k = w.shape[0]
    ke = (k + 1) // 2
    B, H, W, C = x.shape
    hp, wp = 2 * (Ho + ke - 1), 2 * (Wo + ke - 1)
    x = jnp.pad(
        x,
        ((0, 0), (padding, max(0, hp - H - padding)), (padding, max(0, wp - W - padding)), (0, 0)),
    )[:, :hp, :wp]
    x = x.reshape(B, hp // 2, 2, wp // 2, 2, C).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, hp // 2, wp // 2, 4 * C)
    w = jnp.pad(w, ((0, 2 * ke - k), (0, 2 * ke - k), (0, 0), (0, 0)))
    w = w.reshape(ke, 2, ke, 2, C, -1).transpose(0, 2, 1, 3, 4, 5).reshape(ke, ke, 4 * C, -1)
    return x, w


def conv_out_hw(h: int, k: int, stride: int, padding: int) -> int:
    return (h + 2 * padding - k) // stride + 1


@functools.partial(jax.jit, static_argnames=("stride", "padding", "norm", "groups", "act", "eps"))
def conv_block_pallas(
    x,
    w,
    b,
    gamma,
    beta,
    stride: int = 1,
    padding: int = 0,
    norm: str = "batch",
    groups: int = 1,
    act: str = "silu",
    eps: float = 1e-5,
):
    """Fused conv(+bias) + norm + act. x: (B, H, W, Cin) -> (B, Ho, Wo, Cout).

    ``b``/``gamma``/``beta``: (Cout,) conv bias and norm affine (pass zeros/
    ones to disable). Stride 1 or 2; batch norm takes batch statistics.
    """
    B, H, W, _ = x.shape
    k = w.shape[0]
    Cout = w.shape[-1]
    Ho, Wo = conv_out_hw(H, k, stride, padding), conv_out_hw(W, k, stride, padding)
    assert norm in NORMS and act in ACTS, (norm, act)
    if stride == 2:
        x, w = _space_to_depth(x, w, padding, Ho, Wo)
        k, padding = w.shape[0], 0
    elif stride != 1:
        raise ValueError(f"conv_block_pallas supports stride 1 or 2, got {stride}")
    _, Hin, Win, Cin = x.shape
    nb = _samples_per_step(B, norm)
    tn = _channel_tile(Cout, norm)
    kernel = functools.partial(
        _conv_block_kernel,
        k=k, pad=padding, Ho=Ho, Wo=Wo, norm=norm, groups=groups, act=act, eps=eps,
    )
    return pl.pallas_call(
        kernel,
        grid=(B // nb, Cout // tn),
        in_specs=[
            pl.BlockSpec((nb, Hin, Win, Cin), lambda bi, j: (bi, 0, 0, 0)),
            pl.BlockSpec((k, k, Cin, tn), lambda bi, j: (0, 0, 0, j)),
            _vec_spec(tn),
            _vec_spec(tn),
            _vec_spec(tn),
        ],
        out_specs=pl.BlockSpec((nb, Ho, Wo, tn), lambda bi, j: (bi, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Cout), x.dtype),
        compiler_params=backend.compiler_params(2),
        interpret=backend.interpret(),
    )(x, w, *_rows(b, gamma, beta))


def _sppf_kernel(x_ref, o_ref, *, H, W, C, window, reps):
    """SPPF pool pyramid: ``reps`` cascaded stride-1 max pools on one
    sample, concatenated with the input along channels — all in VMEM, one
    write of the (H, W, (reps+1)*C) result. Each pool is window*window
    static slices reduced by max (-inf halo), so padded positions can
    never win: bit-exact vs the reduce_window reference at any dtype."""
    x = x_ref[0]  # (H, W, C)
    pad = window // 2
    neg = jnp.asarray(-jnp.inf, x.dtype)
    outs = [x]
    cur = x
    for _ in range(reps):
        xp = jnp.pad(cur, ((pad, pad), (pad, pad), (0, 0)), constant_values=neg)
        m = None
        for ki in range(window):
            for kj in range(window):
                win = jax.lax.slice(xp, (ki, kj, 0), (ki + H, kj + W, C))
                m = win if m is None else jnp.maximum(m, win)
        cur = m
        outs.append(cur)
    o_ref[0] = jnp.concatenate(outs, axis=-1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "reps"))
def sppf_pyramid_pallas(x, window: int = 5, reps: int = 3):
    """Fused SPPF tail: (B, H, W, C) -> (B, H, W, (reps+1)*C) — the
    concat of the input with ``reps`` cascaded stride-1/same max pools
    (YOLOv8: 5x5, reps=3). Pure max/concat, so exact at any batch."""
    B, H, W, C = x.shape
    kernel = functools.partial(_sppf_kernel, H=H, W=W, C=C, window=window, reps=reps)
    Cout = (reps + 1) * C
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W, C), lambda bi: (bi, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, H, W, Cout), lambda bi: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, Cout), x.dtype),
        compiler_params=backend.compiler_params(1),
        interpret=backend.interpret(),
    )(x)


def _deconv_block_kernel(x_ref, w_ref, b_ref, g_ref, bt_ref, o_ref, *, H, W, norm, groups, act, eps):
    w = w_ref[...]
    bias = b_ref[...].astype(jnp.float32)
    ys = []
    for bi in range(x_ref.shape[0]):
        x_0 = x_ref[bi]  # (H, W, Cin)
        # whole sample per grid step: the +-1 row halos are plain shifts
        if H == 1:
            x_m1 = x_p1 = jnp.zeros_like(x_0)
        else:
            x_m1 = jnp.concatenate([jnp.zeros_like(x_0[:1]), x_0[:-1]], axis=0)
            x_p1 = jnp.concatenate([x_0[1:], jnp.zeros_like(x_0[:1])], axis=0)
        ys.append([ph + bias for ph in _phase_matmuls(x_m1, x_0, x_p1, w, H, W)])
    ys = _norm_act(ys, g_ref[...].astype(jnp.float32), bt_ref[...].astype(jnp.float32),
                   norm=norm, groups=groups, act=act, eps=eps)
    for bi, phases in enumerate(ys):
        for p, ph in enumerate(phases):  # (row parity, column parity) = divmod(p, 2)
            o_ref[bi, p] = ph.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("norm", "groups", "act", "eps"))
def deconv_block_pallas(
    x,
    w,
    b,
    gamma,
    beta,
    norm: str = "batch",
    groups: int = 1,
    act: str = "relu",
    eps: float = 1e-5,
):
    """Fused k=4/stride=2/torch-padding-1 deconv (crop folded) + norm + act.

    x: (B, H, W, Cin) -> (B, 2H, 2W, Cout); weights (4, 4, Cin, Cout).
    The kernel writes the four parity phases phase-major and XLA
    interleaves them: an in-kernel interleave is a relayout Mosaic takes
    minutes to compile at 64x64.
    """
    B, H, W, Cin = x.shape
    assert w.shape[:2] == (4, 4), "phase decomposition is specialized to k=4"
    Cout = w.shape[-1]
    assert norm in NORMS and act in ACTS, (norm, act)
    nb = _samples_per_step(B, norm)
    tn = _channel_tile(Cout, norm)
    kernel = functools.partial(
        _deconv_block_kernel, H=H, W=W, norm=norm, groups=groups, act=act, eps=eps
    )
    y = pl.pallas_call(
        kernel,
        grid=(B // nb, Cout // tn),
        in_specs=[
            pl.BlockSpec((nb, H, W, Cin), lambda bi, j: (bi, 0, 0, 0)),
            pl.BlockSpec((4, 4, Cin, tn), lambda bi, j: (0, 0, 0, j)),
            _vec_spec(tn),
            _vec_spec(tn),
            _vec_spec(tn),
        ],
        out_specs=pl.BlockSpec((nb, 4, H, W, tn), lambda bi, j: (bi, 0, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, 4, H, W, Cout), x.dtype),
        compiler_params=backend.compiler_params(2),
        interpret=backend.interpret(),
    )(x, w[::-1, ::-1], *_rows(b, gamma, beta))
    y = y.reshape(B, 2, 2, H, W, Cout).transpose(0, 3, 1, 4, 2, 5)
    return y.reshape(B, 2 * H, 2 * W, Cout)
