"""Run one cell of the chip benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json``; each metric is read by ``bench/end_to_end/<name>.py``
(``--trace 0``) or ``bench/layer_metrics/<name>.py`` (``--trace 1``). The
run needs a TPU with the cell's chip count: without one it exits non-zero
and prints no result. The last lines on standard error are the numbers
compared with the reference, each beside its limit; the last line on
standard output is one JSON object with ``correct``, ``attempted``,
``failed`` (frames admitted that never completed), ``dropped`` (frames that
admission refused), ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, the same numbers and limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the persistent compile cache lives in the checkout, at a fixed path; set
# before JAX is imported so that JAX and the program both take it
CACHE = str(ROOT / ".jax_cache")


def read_metric(kind: str, name: str, rec: dict):
    """Value of metric ``name`` from its reader ``bench/<kind>/<name>.py``;
    None when the reader finds nothing to read."""
    path = ROOT / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def result(spec: dict, rec: dict, trace: bool, device: dict) -> dict:
    from bench import check
    from bench.stats import completed

    # a frame that admission drops is answered at once, by design, and
    # counts against goodput_fps; a failed frame is one that was admitted
    # and never came back
    attempted = len(rec["arrivals"])
    dropped = sum(a.decision == "drop" for a in rec["arrivals"])
    failed = attempted - dropped - len(completed(rec))
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        v = read_metric("layer_metrics" if trace else "end_to_end", m["name"], rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {
        "correct": check.correct(rec["checks"]),
        "attempted": attempted,
        "failed": failed,
        "dropped": dropped,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        out["breakdown"] = {k: rec["trace"][k] for k in ("device_ops", "idle_gaps")}
    out["checks"] = {
        k: {"value": c["value"] if math.isfinite(c["value"]) else 1e30, "limit": c["limit"]}
        for k, c in rec["checks"].items()
    }
    return out


def summary(rec: dict) -> dict:
    """Numbers printed before the result: what decides nothing, but explains."""
    from bench.stats import completed, percentile

    lat = [a.latency_due_s * 1e3 for a in completed(rec)]
    return {
        "latency_p50_ms": percentile(lat, 50), "latency_p95_ms": percentile(lat, 95),
        "latency_p99_ms": percentile(lat, 99), "completed": len(lat),
        "backlog_at_horizon": rec["backlog_at_horizon"], "drain_s": rec["drain_s"],
        "window_compiles": rec["window_compiles"], "admission": rec["report"]["admission"],
        "gc_pauses": {"count": len(rec["gc_pauses"]), "gen2": sum(g == 2 for g, _ in rec["gc_pauses"]),
                      "total_s": sum(d for _, d in rec["gc_pauses"]),
                      "longest_s": max((d for _, d in rec["gc_pauses"]), default=0.0)},
        "host_stalls": _stalls(rec),
        "setup_phases_s": rec["setup_phases_s"], "check_s": rec["check_s"],
        "trace": None if rec["trace"] is None else {
            k: rec["trace"][k] for k in ("window_s", "busy_s", "idle_share", "idle_by_span_s")},
        "spans": None if rec.get("spans") is None else {
            k: rec["spans"][k] for k in ("idle_host_busy_pct", "idle_by_span")},
    }


def _stalls(rec: dict) -> dict:
    """Passes of the window's loop over ``harness.STALL_S``, in the window."""
    st = [s for s in rec["stalls"] if s["at_s"] < rec["seconds"]]
    drops: dict = {}
    for a in rec["arrivals"]:
        if a.decision == "drop":
            drops[int(a.t_due)] = drops.get(int(a.t_due), 0) + 1
    return {"count": len(st), "total_s": sum(s["wall_s"] for s in st),
            "longest": sorted(st, key=lambda s: -s["wall_s"])[:5], "drops_by_second": drops}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    from bench import harness, spec as spec_mod

    spec = spec_mod.load(args.workload)
    import jax

    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    from bench.peaks import peaks
    from repro.launch import compile_cache

    compile_cache.enable()
    row = peaks(devices[0].device_kind)
    rec = harness.run(spec, args.seed, args.seconds, bool(args.trace), T_START)
    rec["peaks"], rec["peak"] = row, row["bf16_flops_per_s"]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    if args.trace:
        device.update(busy_s=rec["trace"]["busy_mean_s"], window_s=rec["trace"]["window_s"])
    out = result(spec, rec, bool(args.trace), device)
    print(json.dumps({"summary": summary(rec), "compile_cache": CACHE}), file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"{k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
