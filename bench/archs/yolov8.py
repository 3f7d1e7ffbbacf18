"""YOLOv8 (Ultralytics ``yolov8.yaml``): Conv (conv, norm, SiLU) stem and
downsamples, C2f blocks, SPPF, the PAN neck with nearest 2x upsampling,
and three decoupled heads (box ``4 * reg_max`` and class logits).

Departures, each one what the served configuration runs:

* its batch norms take per-frame statistics at inference
  (``reference.norm``; no running statistics);
* its heads are ``c2 = max(16, c_in, 4 reg_max)`` and ``c3 = c_in`` wide
  per level, where Ultralytics shares ``c2 = max(16, ch0 / 4, 4
  reg_max)`` and ``c3 = max(ch0, nc)`` across levels: the P4 and P5 heads
  are wider than published. ``flops`` counts the published widths: the
  count is of the published model's work, whatever an implementation
  executes.

Its request is one CT phantom, the default pool.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import flops as F
from ..reference import conv, max_pool5, norm, norm_shapes, upsample2


def dims(cfg):
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


def forward(p, x, cfg, o=None):
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


def shapes(cfg):
    (c1, c2, c3, c4, c5), n = dims(cfg)
    reg, nc = cfg["reg_max"], cfg["n_classes"]

    def cb(ci, co, k):
        return {"conv": {"w": (k, k, ci, co)}, "bn": norm_shapes(co, "batch")}

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


def flops(cfg: dict) -> float:
    def ch(x):
        return max(16, int(round(min(x, 1024) * cfg["width"] / 8)) * 8)

    def n(k):
        return max(1, round(k * cfg["depth"]))

    total = 0.0

    def cb(h, ci, co, k, s=1):
        nonlocal total
        f, out = F.conv(h, ci, co, k, s)
        total += f
        return out

    def c2f(h, ci, co, nb):
        c_h = co // 2
        cb(h, ci, co, 1)
        for _ in range(nb):
            cb(h, c_h, c_h, 3)
            cb(h, c_h, c_h, 3)
        cb(h, (2 + nb) * c_h, co, 1)

    c1, c2, c3, c4, c5 = (ch(c) for c in (64, 128, 256, 512, 1024))
    h = cb(cfg["img_size"], 3, c1, 3, 2)
    h = cb(h, c1, c2, 3, 2)
    c2f(h, c2, c2, n(3))
    h3 = cb(h, c2, c3, 3, 2)
    c2f(h3, c3, c3, n(6))
    h4 = cb(h3, c3, c4, 3, 2)
    c2f(h4, c4, c4, n(6))
    h5 = cb(h4, c4, c5, 3, 2)
    c2f(h5, c5, c5, n(3))
    cb(h5, c5, c5 // 2, 1)  # SPPF
    cb(h5, 2 * c5, c5, 1)
    c2f(h4, c5 + c4, c4, n(3))
    c2f(h3, c4 + c3, c3, n(3))
    cb(h3, c3, c3, 3, 2)
    c2f(h4, c3 + c4, c4, n(3))
    cb(h4, c4, c4, 3, 2)
    c2f(h5, c4 + c5, c5, n(3))
    reg, nc = cfg["reg_max"], cfg["n_classes"]
    w2, w3 = max(16, c3 // 4, 4 * reg), max(c3, min(nc, 100))
    for h, c in ((h3, c3), (h4, c4), (h5, c5)):
        cb(h, c, w2, 3)
        cb(h, w2, w2, 3)
        cb(h, w2, 4 * reg, 1)
        cb(h, c, w3, 3)
        cb(h, w3, w3, 3)
        cb(h, w3, nc, 1)
    return total
