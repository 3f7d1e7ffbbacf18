"""Reduction of a profiler trace to device busy time, idle share and gaps.

A trace is reduced from a compact form, ``{"device": {plane: [[name,
start_ns, dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...]}``,
which ``load`` reads out of the profiler's ``.xplane.pb``: one list of
operations per TPU chip and the benchmark's own host spans (``SPANS``,
``jax.profiler.TraceAnnotation`` around each step of the driver loop).

* The window is the extent of the host spans: the driver loop, traced.
* A chip's busy time is the union of its operations' intervals inside the
  window; its idle share is one minus busy over the window.
* Each idle gap is attributed to the host span that overlaps it most:
  what the driver was doing while the chip waited.
"""
from __future__ import annotations

import bisect
import glob
import os

SPANS = ("bench.offer", "bench.tick", "bench.collect", "bench.wait")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> dict:
    """The compact trace of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[len(DEVICE_PREFIX):].isdigit():
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE] or list(plane.lines)
            out["device"][plane.name] = [
                [ev.name, ev.start_ns, ev.duration_ns] for ln in lines for ev in ln.events
            ]
        elif plane.name.startswith("/host:"):
            out["host"].extend(
                [ev.name, ev.start_ns, ev.duration_ns]
                for ln in plane.lines for ev in ln.events if ev.name in SPANS
            )
    return out


def op_name(hlo: str) -> str:
    """``%fusion.1 f32[1,258,258,3]`` out of an XLA op event's HLO text."""
    name, _, rest = hlo.partition(" = ")
    return f"{name} {rest.lstrip('(').split('{')[0].split(' ')[0]}" if rest else hlo


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(gap, spans, starts) -> str:
    """The host span overlapping ``gap`` the most (``spans`` sorted by start;
    the driver's spans follow one another, so the scan starts at the last
    span that began before the gap)."""
    best, best_ov = "none", 0.0
    for name, s, e in spans[max(0, bisect.bisect_right(starts, gap[0]) - 1):]:
        if s >= gap[1]:
            break
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle time per chip over the traced window, the top device
    operations by time, and the longest idle gaps by host span."""
    spans = sorted((n, float(s), float(s) + float(d)) for n, s, d in trace["host"])
    if not spans or not trace["device"]:
        raise ValueError("trace has no host spans or no device operations")
    w0, w1 = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    spans.sort(key=lambda t: t[1])
    starts = [s for _, s, _ in spans]
    busy, gaps, op_time = [], [], {}
    for plane in sorted(trace["device"]):
        ivs = []
        for name, s, d in trace["device"][plane]:
            s, e = max(float(s), w0), min(float(s) + float(d), w1)
            if e > s:
                ivs.append((s, e))
                key = op_name(name)
                op_time[key] = op_time.get(key, 0.0) + (e - s)
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label((s, e), spans, starts)))
    window = w1 - w0
    by_span: dict = {}
    for d, name in gaps:
        by_span[name] = by_span.get(name, 0.0) + d / len(busy)
    return {
        "window_s": window / 1e9,
        "busy_s": [b / 1e9 for b in busy],
        "busy_mean_s": sum(busy) / len(busy) / 1e9,
        "idle_share": 1.0 - sum(busy) / len(busy) / window,
        "idle_by_span_s": {k: v / 1e9 for k, v in sorted(by_span.items())},
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, d / 1e9] for d, n in sorted(gaps, key=lambda g: -g[0])[:top]],
    }
