"""Knee sweep of one configuration on the chip: offered rate against deadline.

    python bench/sweep.py --config pix2pix_bn_yolov8n --traffic poisson_200hz --rates 100,200,400 --seconds 8

Builds the configuration once and warms it up, then drives one open-loop
window per aggregate rate with the mix ``bench/traffic/<traffic>.json``,
its ``aggregate_hz`` set to the rate, and prints a JSON line per rate: the
end-to-end metrics as their readers under ``bench/end_to_end/`` read the
window, the share of due frames that met the deadline, the backlog when
the last arrival was offered, and admission's counts. The knee is the
highest rate at which at least 90% of due frames meet the deadline and the
backlog does not grow; the cells' fixed rates are set from it once, by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True, help="a mix under bench/traffic/")
    ap.add_argument("--rates", required=True, help="aggregate offered rates, frames/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from bench import archs, arrivals, harness, inputs
    from bench.run import read_metric
    from repro.launch import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    compile_cache.enable()
    config = json.loads((ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / f"{args.traffic}.json").read_text())
    cell = harness.Cell(config)
    cell.load_weights(args.seed)
    pools = archs.pools(cell.models, args.seed)
    cell.warm_up(pools)
    print(json.dumps({"config": args.config, "warm": compile_cache.stats()}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        sched = arrivals.schedule(dict(mix, aggregate_hz=rate), cell.streams, args.seed, args.seconds,
                                  inputs.POOL)
        timing = cell.window(sched, pools, args.seconds)
        rec = {"arrivals": sched, "seconds": args.seconds, "deadline_s": cell.deadline_s}
        out = {m: read_metric("end_to_end", m, rec)
               for m in ("goodput_fps", "latency_p50_ms", "latency_p95_ms")}
        rep = timing["report"]
        print(json.dumps({
            "offered_hz": rate, "due": len(sched), **out,
            "met_share": out["goodput_fps"] * args.seconds / max(1, len(sched)),
            "backlog_at_horizon": timing["backlog_at_horizon"], "drain_s": timing["end_s"] - args.seconds,
            "admission": rep["admission"], "mean_batch": rep["batching"]["mean_effective_batch"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
