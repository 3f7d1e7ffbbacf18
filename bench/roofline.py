"""A kernel's share of its roofline on the chip.

The least time the chip could take for a kernel's work is the larger of
its operations over the peak rate of operations and its bytes over the
peak memory bandwidth (``peaks.json``); the share is that time over the
time the kernel took on the device. Nothing clamps it: a share over 100%
means the operations or bytes are counted too high, or the measured time
leaves out part of the kernel's work.

Operations and bytes per request come from the architecture's
``kernels(cfg)`` (``archs/__init__.py``); device time per operation from
the traced run's record (``rec["spans"]["op_time"]``).
"""
from __future__ import annotations

from . import archs
from .stats import tail_completed


def share_pct(time_s: float, flops: float, bytes_: float, peaks: dict) -> float | None:
    """Least time at the chip's bf16 peak and HBM bandwidth over ``time_s``,
    in %; None where nothing was timed."""
    if not time_s > 0:
        return None
    least = max(flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / time_s


def kernel_time_s(rec: dict, match) -> float:
    """Device seconds of the traced tail's operations that ``match(module, op)`` selects."""
    return sum(t for mod, ops in rec["spans"]["op_time"].items() for op, t in ops.items() if match(mod, op))


def kernel_share_pct(rec: dict, model: str, kernel: str) -> float | None:
    """Roofline share of ``kernel`` of the configuration's model ``model``
    over the traced tail: its operations and bytes per request times the
    model's requests completed in the tail, against its device time. None
    where the tail timed none of it or completed no request."""
    if rec.get("spans") is None:
        return None
    cfg = next(m for m in rec["models"] if m["name"] == model)
    match, flops, bytes_ = archs.kernels(cfg)[kernel]
    n = len(tail_completed(rec, model))
    t = kernel_time_s(rec, match)
    return share_pct(t, n * flops, n * bytes_, rec["peaks"]) if n and t else None
