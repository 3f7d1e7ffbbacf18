"""One traced run of a cell with the program's span recorder on.

    python bench/spantrace.py --workload <cell> --seed <n> --seconds <s> [--out DIR]

``DIR`` defaults to ``spantrace_out/`` in the checkout.

It runs what ``bench/run.py --trace 1`` runs, through the same
``harness.measure``, with the server's ``SpanRecorder`` on for the window
and the traced tail, and prints the same result line on standard output.
Beside it, on standard error and in ``DIR/<cell>.<seed>.json``, it gives
what the program's spans show (``bench/spans.py``):

* device time per segment executable, each frame's share of it per model,
  and the op scopes of the top device operations;
* device idle time by program span, and idle time while the host is inside
  ``serve.tick`` and outside ``executor.block``;
* how long each tick of the tail ran before its first device operation,
  and which spans that time fell in;
* every host stall of the window (a pass of the window's loop over
  ``harness.STALL_S``) with the deepest span covering most of it, from the
  recorder's record of long spans, and every ``serve.tick`` of the tail
  over ``STALL_S`` with its owner from the device trace;
* the recorder's counters over the window.

It also writes ``DIR/<cell>.<seed>.trace.json``, 25 ms of the tail's
compact trace (``bench/testdata/program_spans_v5e_25ms.json`` is one). The
run needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SLICE_S = 0.025  # length of the compact trace slice written beside the summary
_OP_NAME = re.compile(r'^\s*(?:ROOT )?(%\S+) = .*?op_name="([^"]*)"')


def _slice(prog: dict, t0: float, t1: float) -> dict:
    """The events of ``prog`` overlapping ``[t0, t1)``, each op named as
    ``trace.op_name`` names it."""
    from bench.trace import op_name

    def inside(s, d):
        return float(s) < t1 and float(s) + float(d) > t0

    return {"ops": {p: [[op_name(o[0]), *o[1:]] for o in ops if inside(o[1], o[2])]
                    for p, ops in prog["ops"].items()},
            "spans": [sp for sp in prog["spans"] if inside(sp[1], sp[2])]}


def op_scopes(cell, top) -> dict:
    """``{module: {op: named_scope path}}`` for the ``(op, module, ...)``
    rows of ``top``, read from the compiled text of each segment executable
    at every batch the executor warmed (one module name covers them all;
    an op whose scope differs between them gets each, joined by ``|``)."""
    import jax

    from repro.core.pipeline import executable_label

    ex = cell.executors[0]
    want: dict = {}
    for op, mod, *_ in top:
        want.setdefault(mod, set()).add(op.lstrip("%"))
    out: dict = {}
    for mi, model in enumerate(cell.bundle.models):
        if not ex._state_structs[mi]:
            continue
        label = re.escape(executable_label(model.name))
        for mod, ops in want.items():
            m = re.fullmatch(rf"jit_{label}\.(\d+)_(\d+)\.(\w+)", mod)
            if not m:
                continue
            lo, hi, impl = int(m[1]), int(m[2]), m[3]
            found: dict = {}
            for struct in ex._warm_structs(mi, ex._state_structs[mi][0][1]):
                if lo:
                    struct = jax.eval_shape(model.segment_fn(0, lo, impl), model.params, struct)
                fn = model.jitted_segment_fn(lo, hi, donate=ex._donate, impl=impl)
                for line in fn.lower(model.params, struct).compile().as_text().splitlines():
                    hit = _OP_NAME.match(line)
                    if hit and hit[1].lstrip("%") in ops:
                        found.setdefault(hit[1].lstrip("%"), set()).add(hit[2].split("/", 1)[-1])
            out[mod] = {op: "|".join(sorted(v)) for op, v in found.items()}
    return out


def trace_run(cell, spec: dict, seed: int, seconds: float, t_start=None, cs0=None,
              release: bool = True) -> tuple[dict, dict, dict]:
    """``harness.measure`` with the recorder on; returns the run record,
    what the spans show, and the tail's compact trace."""
    from bench import harness, spans
    from repro.core.pipeline import executable_label

    grabbed: dict = {}
    scratch = tempfile.mkdtemp(prefix="spantrace_")
    saved = tempfile.tempdir

    def grab(c, sched, frames):
        # measure makes its trace directory under tempfile.tempdir: the only one here
        (d,) = glob.glob(os.path.join(scratch, "bench_trace_*"))
        grabbed["prog"] = spans.load(d)
        grabbed["model_of"] = dict(c.model_of)
        grabbed["labels"] = [executable_label(m.name) for m in c.bundle.models]
        c.server.tracer.disable()
        grabbed["scopes"] = op_scopes(c, spans.top_ops(grabbed["prog"]))

    cell.server.tracer.enable()
    tempfile.tempdir = scratch
    try:
        rec = harness.measure(cell, spec, seed, seconds, True, t_start, cs0, after_window=grab,
                              release=release)
    finally:
        tempfile.tempdir = saved
        if cell.server is not None:
            cell.server.tracer.disable()
        shutil.rmtree(scratch, ignore_errors=True)
    return rec, explain(rec, grabbed), grabbed


def explain(rec: dict, grabbed: dict) -> dict:
    """What the program's spans show about one traced run."""
    from bench import harness, spans

    prog, model_of, labels = grabbed["prog"], grabbed["model_of"], grabbed["labels"]
    w0, w1 = spans.window(prog)
    mods = spans.module_time(prog)
    lo, hi = rec["traced_s"]
    frames = [0] * len(labels)
    for a in rec["arrivals"] + rec["tail"]:
        if a.done and lo <= a.t_offer + a.latency_s <= hi:
            frames[model_of[a.stream]] += 1
    per_model = {}
    for mi, label in enumerate(labels):
        t = sum(s for m in mods.values() for mod, s in m["modules"].items() if mod.startswith(f"jit_{label}."))
        per_model[label] = {"device_s": t, "frames": frames[mi],
                            "device_ms_per_frame": 1e3 * t / frames[mi] if frames[mi] else None}
    busy = sum(m["busy_s"] for m in mods.values())
    named = sum(s for m in mods.values() for mod, s in m["modules"].items()
                if any(mod.startswith(f"jit_{lb}.") for lb in labels))
    summed = sum(s for m in mods.values() for s in m["modules"].values())

    # each traced tick: its lead before its first device operation, and the
    # spans that lead fell in
    ops = sorted(float(o[1]) for p in prog["ops"].values() for o in p)
    ticks = [(float(s), float(s) + float(d)) for n, s, d, _ in prog["spans"] if n == "serve.tick"]
    named_spans = [(n, float(s), float(s) + float(d)) for n, s, d, _ in prog["spans"]]
    leads, lead_in = [], {}
    for s, e in ticks:
        k = bisect.bisect_left(ops, s)
        first = ops[k] if k < len(ops) and ops[k] < e else None
        if first is None:
            continue
        leads.append((first - s) / 1e6)
        for n, a, b in named_spans:
            ov = min(b, first) - max(a, s)
            if ov > 0 and n != "serve.tick" and not n.startswith(spans.LOOP):
                lead_in[n] = lead_in.get(n, 0.0) + ov / 1e6
    stall_ns = harness.STALL_S * 1e9
    tail_stalls = []
    for s, e in ticks:
        if e - s > stall_ns:
            inside = [(n, a, b) for n, a, b in named_spans if a >= s and b <= e]
            who, share = spans.owner(inside, s, e)
            tail_stalls.append({"at_s": (s - w0) / 1e9, "wall_s": (e - s) / 1e9, "owner": who, "share": share})

    recorded = rec["report"].get("spans") or {}
    long = recorded.get("long", [])
    long_iv = [(sp["name"], sp["start_s"], sp["start_s"] + sp["dur_s"]) for sp in long]
    window_stalls = []
    for st in rec["stalls"]:
        if st["at_s"] >= rec["seconds"]:
            continue
        a, b = st["at_s"], st["at_s"] + st["wall_s"]
        who, share = spans.owner(long_iv, a, b)
        window_stalls.append({"at_s": a, "wall_s": st["wall_s"], "phase": st["phase"],
                              "owner": who, "share": share,
                              "chain": [[n, round(min(e, b) - max(s, a), 6)] for n, s, e in long_iv
                                        if min(e, b) > max(s, a)]})
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy,
        "modules": mods,
        "module_sum_over_busy": summed / busy if busy else None,
        "named_share_of_busy": named / busy if busy else None,
        "per_model": per_model,
        "top_ops": [[op, mod, grabbed.get("scopes", {}).get(mod, {}).get(op.lstrip("%"), scope), t]
                    for op, mod, scope, t in spans.top_ops(prog)],
        "idle_by_program_span_s": spans.idle_by_span(prog),
        "device.idle_host_busy_pct": spans.idle_host_busy_pct(prog),
        "tick_lead_ms": {"ticks": len(leads), "mean": sum(leads) / len(leads) if leads else None,
                         "max": max(leads, default=None),
                         "in_span_ms_per_tick": {n: v / len(leads) for n, v in sorted(lead_in.items())}
                         if leads else {}},
        "window_stalls": window_stalls,
        "long_spans": {"kept": len(long), "first_s": min((sp["start_s"] for sp in long), default=None),
                       "trees_dropped": recorded.get("long_dropped")},
        "tail_stalls": tail_stalls,
        "counters": recorded.get("counters", {}),
        "queue": rec["report"].get("queue"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=str(ROOT / "spantrace_out"))
    args = ap.parse_args(argv)

    from bench import run

    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE
    from bench import harness, spans, spec as spec_mod

    spec = spec_mod.load(args.workload)
    import jax

    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"spantrace: {args.workload} needs {chips} TPU chips; JAX sees {devices}", file=sys.stderr)
        return 2
    from bench.peaks import peaks
    from repro.launch import compile_cache

    compile_cache.enable()
    cs0 = compile_cache.stats()
    cell = harness.Cell(spec["config"])
    rec, shown, grabbed = trace_run(cell, spec, args.seed, args.seconds, T_START, cs0)
    row = peaks(devices[0].device_kind)
    rec["peaks"], rec["peak"] = row, row["bf16_flops_per_s"]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": rec["memory_peak_bytes"],
              "busy_s": rec["trace"]["busy_mean_s"], "window_s": rec["trace"]["window_s"]}
    out = run.result(spec, rec, True, device)
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, f"{args.workload}.{args.seed}")
    with open(stem + ".json", "w") as f:
        json.dump({"result": out, "summary": run.summary(rec), "spans": shown}, f, indent=1, default=str)
    prog = grabbed["prog"]
    w0, w1 = spans.window(prog)
    mid = (w0 + w1) / 2
    with open(stem + ".trace.json", "w") as f:
        json.dump(dict(_slice(prog, mid, mid + SLICE_S * 1e9),
                       source=f"{device['kind']}, {args.workload}, seed {args.seed}, {SLICE_S * 1e3:g} ms "
                              "of the traced tail, bench/spantrace.py"), f, default=str)
    print(json.dumps({"spans": {k: v for k, v in shown.items() if k not in ("modules", "counters")}},
                     default=str), file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
