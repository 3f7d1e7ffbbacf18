"""Plain references of the served models, in ``jax.numpy`` and ``jax.lax`` alone.

Written from the published architectures, independent of the program:

* Pix2Pix U-Net generator (Isola et al. 2017, pix2pix ``unet_256``; TF
  tutorial layout): ``log2(img)`` stride-2 4x4 conv blocks (no norm on the
  first), leaky ReLU 0.2; mirrored 4x4 stride-2 transposed convs with one
  border row and column cropped (torch ``padding=1``, TF ``same``), norm,
  ReLU, skip concat; a biased final transposed conv and tanh.
* YOLOv8 (Ultralytics ``yolov8.yaml``): Conv (conv, norm, SiLU) stem and
  downsamples, C2f blocks, SPPF, the PAN neck with nearest 2x upsampling,
  and three decoupled heads (box ``4 * reg_max`` and class logits).

Departures, each one what the served configuration runs:

* Norm layers take per-frame statistics over (H, W) at inference. That is
  the pix2pix original's batch norm in training mode at batch 1 and the
  instance norm of the batch-independent variant; the served batch-norm
  models run one frame per flight, so their statistics are per frame too.
  YOLOv8's batch norms are served the same way (no running statistics).
* YOLOv8's heads are ``c2 = max(16, c_in, 4 reg_max)`` and ``c3 = c_in``
  wide per level, where Ultralytics shares ``c2 = max(16, ch0 / 4, 4
  reg_max)`` and ``c3 = max(ch0, nc)`` across levels: the P4 and P5 heads
  are wider than published (``flops.py`` counts the published widths).

Parameters are nested dicts shaped as the served program holds them, so
that the benchmark can make one set from a seed and give it to both.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
from jax import lax

DN = ("NHWC", "HWIO", "NHWC")
EPS = 1e-5


# -- layers -------------------------------------------------------------------


def _operands(x, w, o):
    """Conv operands as the chip's MXU takes them: rounded to ``o`` (None:
    as they are). Products of ``bfloat16`` values are exact in float32, so
    under ``highest`` precision the sum is the float32 accumulation of the
    rounded operands' products: what default precision computes on a TPU."""
    return (x, w) if o is None else (x.astype(o).astype(x.dtype), w.astype(o).astype(x.dtype))


def conv(x, w, stride=1, pad=0, b=None, o=None):
    x, w = _operands(x, w.astype(x.dtype), o)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)], dimension_numbers=DN
    )
    return y if b is None else y + b.astype(x.dtype)


def deconv_cropped(x, w, b=None, o=None):
    """k x k stride-2 transposed conv, one border row and column cropped
    from each side: output side ``2 * input``."""
    k = w.shape[0]
    x, w = _operands(x, w.astype(x.dtype), o)
    y = lax.conv_general_dilated(
        x, w, (1, 1), [(k - 2, k - 2), (k - 2, k - 2)],
        lhs_dilation=(2, 2), dimension_numbers=DN,
    )
    return y if b is None else y + b.astype(x.dtype)


def norm(x, p):
    """Per-frame, per-channel statistics over (H, W)."""
    mean = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(1, 2), keepdims=True)
    y = (x - mean) * lax.rsqrt(var + EPS)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def max_pool5(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 5, 5, 1), (1, 1, 1, 1), [(0, 0), (2, 2), (2, 2), (0, 0)]
    )


def upsample2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


# -- Pix2Pix U-Net generator ----------------------------------------------------


def pix2pix_channels(cfg):
    b = cfg["base"]
    downs = [min(8 * b, b * 2**i) for i in range(int(math.log2(cfg["img_size"])))]
    return downs, downs[:-1][::-1]


def pix2pix(p, x, cfg, o=None):
    downs, ups = pix2pix_channels(cfg)
    skips = []
    for i in range(len(downs)):
        blk = p["downs"][i]
        x = conv(x, blk["conv"]["w"], 2, 1, o=o)
        if i:
            x = norm(x, blk["bn"])
        x = jnp.where(x >= 0, x, 0.2 * x)
        skips.append(x)
    for i in range(len(ups)):
        blk = p["ups"][i]
        x = jax.nn.relu(norm(deconv_cropped(x, blk["up"]["deconv"]["w"], o=o), blk["bn"]))
        x = jnp.concatenate([x, skips[-2 - i]], axis=-1)
    f = p["final"]["deconv"]
    return jnp.tanh(deconv_cropped(x, f["w"], f["b"], o=o))


def _norm_shapes(c, norm_kind):
    if norm_kind == "batch":
        return {"scale": (c,), "bias": (c,), "moving_mean": (c,), "moving_var": (c,)}
    return {"scale": (c,), "bias": (c,)}


def pix2pix_shapes(cfg):
    downs, ups = pix2pix_channels(cfg)
    k, nk = 4, cfg["norm"]
    d, c_prev = [], cfg["in_channels"]
    for i, ch in enumerate(downs):
        blk = {"conv": {"w": (k, k, c_prev, ch)}}
        if i:
            blk["bn"] = _norm_shapes(ch, nk)
        d.append(blk)
        c_prev = ch
    u = []
    for ch in ups:
        u.append({"up": {"deconv": {"w": (k, k, c_prev, ch)}}, "bn": _norm_shapes(ch, nk)})
        c_prev = 2 * ch
    final = {"deconv": {"w": (k, k, c_prev, cfg["out_channels"]), "b": (cfg["out_channels"],)}}
    return {"downs": d, "ups": u, "final": final}


# -- YOLOv8 -----------------------------------------------------------------------


def yolo_dims(cfg):
    def ch(c):
        return max(16, int(round(c * cfg["width"] / 8)) * 8)

    def n(k):
        return max(1, round(k * cfg["depth"]))

    return [ch(c) for c in (64, 128, 256, 512, 1024)], n


def _cb(p, x, o, stride=1):
    k = p["conv"]["w"].shape[0]
    return jax.nn.silu(norm(conv(x, p["conv"]["w"], stride, k // 2, o=o), p["bn"]))


def _c2f(p, x, o, shortcut):
    y1, y2 = jnp.split(_cb(p["cv1"], x, o), 2, axis=-1)
    outs = [y1, y2]
    for b in p["bn"]:
        y = _cb(b["cv2"], _cb(b["cv1"], outs[-1], o), o)
        outs.append(outs[-1] + y if shortcut else y)
    return _cb(p["cv2"], jnp.concatenate(outs, axis=-1), o)


def _sppf(p, x, o):
    x = _cb(p["cv1"], x, o)
    p1 = max_pool5(x)
    p2 = max_pool5(p1)
    return _cb(p["cv2"], jnp.concatenate([x, p1, p2, max_pool5(p2)], axis=-1), o)


def _head(p, x, o):
    b = _cb(p["box2"], _cb(p["box1"], x, o), o)
    b = conv(b, p["box3"]["w"], b=p["box3"]["b"], o=o)
    c = _cb(p["cls2"], _cb(p["cls1"], x, o), o)
    c = conv(c, p["cls3"]["w"], b=p["cls3"]["b"], o=o)
    return jnp.concatenate([b, c], axis=-1)


def yolov8(p, x, cfg, o=None):
    x = _cb(p["stem"], x, o, 2)
    x = _cb(p["down2"], x, o, 2)
    x = _c2f(p["c2f_2"], x, o, True)
    f3 = _c2f(p["c2f_3"], _cb(p["down3"], x, o, 2), o, True)
    f4 = _c2f(p["c2f_4"], _cb(p["down4"], f3, o, 2), o, True)
    f5 = _sppf(p["sppf"], _c2f(p["c2f_5"], _cb(p["down5"], f4, o, 2), o, True), o)
    u4 = _c2f(p["n_c2f_4"], jnp.concatenate([upsample2(f5), f4], -1), o, False)
    u3 = _c2f(p["n_c2f_3"], jnp.concatenate([upsample2(u4), f3], -1), o, False)
    d4 = _c2f(p["n_c2f_4b"], jnp.concatenate([_cb(p["n_down3"], u3, o, 2), u4], -1), o, False)
    d5 = _c2f(p["n_c2f_5b"], jnp.concatenate([_cb(p["n_down4"], d4, o, 2), f5], -1), o, False)
    return {"p3": _head(p["head3"], u3, o), "p4": _head(p["head4"], d4, o), "p5": _head(p["head5"], d5, o)}


def yolov8_shapes(cfg):
    (c1, c2, c3, c4, c5), n = yolo_dims(cfg)
    reg, nc = cfg["reg_max"], cfg["n_classes"]

    def cb(ci, co, k):
        return {"conv": {"w": (k, k, ci, co)}, "bn": _norm_shapes(co, "batch")}

    def c2f(ci, co, nb):
        h = co // 2
        return {"cv1": cb(ci, co, 1), "bn": [{"cv1": cb(h, h, 3), "cv2": cb(h, h, 3)}
                                             for _ in range(nb)], "cv2": cb((2 + nb) * h, co, 1)}

    def head(ci):
        w2, w3 = max(16, ci, 4 * reg), ci
        return {"box1": cb(ci, w2, 3), "box2": cb(w2, w2, 3),
                "box3": {"w": (1, 1, w2, 4 * reg), "b": (4 * reg,)},
                "cls1": cb(ci, w3, 3), "cls2": cb(w3, w3, 3),
                "cls3": {"w": (1, 1, w3, nc), "b": (nc,)}}

    return {
        "stem": cb(3, c1, 3), "down2": cb(c1, c2, 3), "c2f_2": c2f(c2, c2, n(3)),
        "down3": cb(c2, c3, 3), "c2f_3": c2f(c3, c3, n(6)),
        "down4": cb(c3, c4, 3), "c2f_4": c2f(c4, c4, n(6)),
        "down5": cb(c4, c5, 3), "c2f_5": c2f(c5, c5, n(3)),
        "sppf": {"cv1": cb(c5, c5 // 2, 1), "cv2": cb(2 * c5, c5, 1)},
        "n_c2f_4": c2f(c5 + c4, c4, n(3)), "n_c2f_3": c2f(c4 + c3, c3, n(3)),
        "n_down3": cb(c3, c3, 3), "n_c2f_4b": c2f(c3 + c4, c4, n(3)),
        "n_down4": cb(c4, c4, 3), "n_c2f_5b": c2f(c4 + c5, c5, n(3)),
        "head3": head(c3), "head4": head(c4), "head5": head(c5),
    }


# -- registry -------------------------------------------------------------------

FORWARD = {"pix2pix_unet": pix2pix, "yolov8": yolov8}
SHAPES = {"pix2pix_unet": pix2pix_shapes, "yolov8": yolov8_shapes}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


_MAKERS: dict = {}  # one compiled weight maker per set of models


def make_weights(models: list[dict], seed: int, dtype=jnp.float32):
    """Every model's parameters from one seed, made on the device in one
    jitted call from one normal draw: conv weights He-normal, conv biases
    and norm shifts ``N(0, 0.1)``, norm scales ``1 + N(0, 0.1)``, running
    statistics 0 and 1 (the served norms never read them)."""
    shapes = [SHAPES[m["arch"]](m) for m in models]
    flat, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    sizes = [math.prod(shape) for _, shape in flat]

    def make(key):
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, off = [], 0
        for (path, shape), n in zip(flat, sizes):
            name, v = path[-1].key, z[off:off + n].reshape(shape)
            off += n
            if name == "w":
                v = v * math.sqrt(2.0 / math.prod(shape[:-1]))
            elif name == "scale":
                v = 1.0 + 0.1 * v
            elif name in ("b", "bias"):
                v = 0.1 * v
            elif name == "moving_var":
                v = jnp.ones(shape, jnp.float32)
            else:
                v = jnp.zeros(shape, jnp.float32)
            out.append(v.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    spec = json.dumps([models, jnp.dtype(dtype).name], sort_keys=True)
    if spec not in _MAKERS:
        _MAKERS[spec] = jax.jit(make)
    return _MAKERS[spec](key)
