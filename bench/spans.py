"""The program's own spans beside the device's operations in a profiler trace.

``trace.py`` reduces a trace to the benchmark loop's view: busy and idle time, and
each idle gap under the one ``bench.*`` span overlapping it most. This
module reads the spans the program writes from inside the serving tick
(``repro.serve.tracing``) and the module each device operation belongs
to. Its compact form is

    {"ops": {plane: [[op, start_ns, dur_ns, module, scope], ...]},
     "spans": [[name, start_ns, dur_ns, {attribute: value}], ...]}

with ``module`` the executable an operation ran in (a segment executable
compiles as ``jit_<model>.<lo>_<hi>.<impl>``) and ``scope`` its op's
``named_scope`` path where the trace gives one. The reductions:

* ``idle_by_span``: each device-idle interval's overlap with the program's
  spans, summed per span name (nested spans each count);
* ``idle_host_busy_pct``: time the chip is idle while the host is inside
  ``serve.tick`` and outside ``executor.block``, over the window;
* ``module_time``: device time per module, beside the busy union;
* ``op_time``: device time of every operation, by module and op;
* ``reduce``: the four above, as a traced run's record keeps them
  (``harness.measure``, ``rec["spans"]``);
* ``owner``: the deepest span covering most of an interval, such as a host
  stall.

The window is the extent of the benchmark loop's ``bench.*`` spans, as in
``trace.reduce``.
"""
from __future__ import annotations

import glob
import os
import re

PROGRAM = ("serve.", "executor.", "python.gc")
LOOP = "bench."
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\(\d+\)$")


def load(trace_dir: str) -> dict:
    """The compact form of the newest ``.xplane.pb`` under ``trace_dir``.

    On a device plane the operations are the ``XLA Ops`` line's events,
    each under its ``hlo_module`` stat or, where it has none, the module of
    the ``XLA Modules`` event around it. A trace with no device plane (the
    CPU's) gives the host events that carry an ``hlo_module`` stat."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: dict = {"ops": {}, "spans": []}
    host_ops: dict = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = {ln.name: ln for ln in plane.lines}
        device = plane.name.startswith("/device:")
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, _SUFFIX.sub("", ev.name))
                      for ev in (lines[MODULES_LINE].events if device and MODULES_LINE in lines else []))
        ops = []
        for ln in plane.lines:
            if ln.name == MODULES_LINE:
                continue
            for ev in ln.events:
                if ev.name.startswith(PROGRAM) or ev.name.startswith(LOOP):
                    st = {k: v for k, v in dict(ev.stats).items() if not k.startswith("_")}
                    out["spans"].append([ev.name, ev.start_ns, ev.duration_ns, st])
                    continue
                if device and OPS_LINE in lines and ln.name != OPS_LINE:
                    continue
                st = dict(ev.stats)
                mod = st.get("hlo_module")
                if mod is None and device:
                    mod = _module_at(mods, ev.start_ns)
                if mod is not None:
                    ops.append([ev.name, ev.start_ns, ev.duration_ns, str(mod),
                                str(st.get("tf_op", st.get("name", "")))])
        if ops:
            (out["ops"] if device else host_ops)[plane.name] = ops
    if not out["ops"]:
        out["ops"] = host_ops
    out["spans"].sort(key=lambda sp: sp[1])
    return out


def _module_at(mods, t) -> str:
    for s, e, name in mods:
        if s <= t < e:
            return name
        if s > t:
            break
    return ""


def _union(ivs):
    merged = []
    for s, e in sorted(ivs):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a, b) -> float:
    """Total overlap of two sorted, disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _minus(a, b):
    """Sorted disjoint intervals ``a`` less ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def window(trace: dict) -> tuple[float, float]:
    """The extent of the benchmark loop's spans (of all spans, where it wrote none)."""
    sp = [(s, s + d) for n, s, d, _ in trace["spans"] if n.startswith(LOOP)] or \
         [(s, s + d) for _, s, d, _ in trace["spans"]]
    if not sp or not trace["ops"]:
        raise ValueError("trace has no spans or no device operations")
    return float(min(s for s, _ in sp)), float(max(e for _, e in sp))


def _busy(ops, w0, w1):
    return _union([(max(float(s), w0), min(float(s) + float(d), w1)) for _, s, d, _, _ in ops
                   if min(float(s) + float(d), w1) > max(float(s), w0)])


def _spans_of(trace, name, w0, w1):
    return _union([(max(float(s), w0), min(float(s) + float(d), w1)) for n, s, d, _ in trace["spans"]
                   if n == name and min(float(s) + float(d), w1) > max(float(s), w0)])


def idle_by_span(trace: dict) -> dict:
    """Seconds of device idle time inside each program span name, mean over
    planes; ``"none"`` is idle time inside no program span."""
    w0, w1 = window(trace)
    names = sorted({n for n, *_ in trace["spans"] if n.startswith(PROGRAM)})
    unions = {n: _spans_of(trace, n, w0, w1) for n in names}
    anywhere = _union([iv for u in unions.values() for iv in u])
    out: dict = {}
    planes = trace["ops"]
    for ops in planes.values():
        idle = _minus([[w0, w1]], _busy(ops, w0, w1))
        for n in names:
            out[n] = out.get(n, 0.0) + _overlap(idle, unions[n]) / 1e9 / len(planes)
        out["none"] = out.get("none", 0.0) + sum(e - s for s, e in _minus(idle, anywhere)) / 1e9 / len(planes)
    return out


def idle_host_busy_pct(trace: dict) -> float:
    """Device idle time while the host is inside ``serve.tick`` and outside
    ``executor.block``, over the window, in %, mean over planes."""
    w0, w1 = window(trace)
    host = _minus(_spans_of(trace, "serve.tick", w0, w1), _spans_of(trace, "executor.block", w0, w1))
    tot = 0.0
    for ops in trace["ops"].values():
        idle = _minus([[w0, w1]], _busy(ops, w0, w1))
        tot += _overlap(idle, host)
    return 100.0 * tot / len(trace["ops"]) / (w1 - w0)


def module_time(trace: dict) -> dict:
    """Per plane: the busy union and the device time of each module, in the
    window, in seconds."""
    w0, w1 = window(trace)
    out = {}
    for plane, ops in sorted(trace["ops"].items()):
        per: dict = {}
        for _, s, d, mod, _ in ops:
            t = min(float(s) + float(d), w1) - max(float(s), w0)
            if t > 0:
                per[mod or "none"] = per.get(mod or "none", 0.0) + t / 1e9
        busy = sum(e - s for s, e in _busy(ops, w0, w1)) / 1e9
        out[plane] = {"busy_s": busy, "modules": dict(sorted(per.items(), key=lambda kv: -kv[1]))}
    return out


def op_time(trace: dict) -> dict:
    """``{module: {op: seconds}}``: the device time of every operation in
    the window, summed over planes, each op named as ``trace.op_name``
    names it (``%fusion.1 f32[1,258,258,3]``)."""
    from .trace import op_name

    w0, w1 = window(trace)
    out: dict = {}
    for ops in trace["ops"].values():
        for name, s, d, mod, _ in ops:
            t = min(float(s) + float(d), w1) - max(float(s), w0)
            if t > 0:
                per = out.setdefault(mod or "none", {})
                key = op_name(name)
                per[key] = per.get(key, 0.0) + t / 1e9
    return out


def reduce(trace: dict) -> dict:
    """What a traced run's record keeps of the program's spans and the
    device's operations over the window: ``idle_by_span``,
    ``idle_host_busy_pct``, ``module_time`` and ``op_time``."""
    return {"idle_by_span": idle_by_span(trace), "idle_host_busy_pct": idle_host_busy_pct(trace),
            "module_time": module_time(trace), "op_time": op_time(trace)}


def top_ops(trace: dict, n: int = 10) -> list:
    """The ``n`` operations with the most device time: op, module, scope, seconds."""
    w0, w1 = window(trace)
    acc: dict = {}
    for ops in trace["ops"].values():
        for name, s, d, mod, scope in ops:
            t = min(float(s) + float(d), w1) - max(float(s), w0)
            if t > 0:
                k = (name.split(" = ")[0], mod, scope)
                acc[k] = acc.get(k, 0.0) + t / 1e9
    return [[*k, t] for k, t in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def owner(spans, start: float, end: float, share: float = 0.5):
    """The deepest (shortest) of ``spans`` — ``(name, start, end)`` tuples —
    that covers at least ``share`` of ``[start, end)``, with the part it
    covers; ``("none", 0.0)`` where none does."""
    best = None
    for name, s, e in spans:
        cov = (min(e, end) - max(s, start)) / (end - start) if end > start else 0.0
        if cov >= share and (best is None or e - s < best[2]):
            best = (name, cov, e - s)
    return ("none", 0.0) if best is None else (best[0], best[1])
