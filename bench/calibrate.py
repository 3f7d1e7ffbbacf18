"""Readings that the correctness limits are set from, on the chip.

    python bench/calibrate.py --workload pix2pix_bn_yolov8n.live --seeds 1,2,3 --seconds 5

Builds the cell once. For each seed it makes a run as ``run.py`` does,
with that seed's weights, frames and arrivals at the cell's own load for
``--seconds`` (``harness.measure``), and prints one JSON line with, per
model, the number ``check.py`` compares:

* ``program``: the served sample's reading, and whether the run is
  ``correct`` (the lower reading);
* ``control``: the same comparison after the reference computed in the
  configuration's ``control`` type (bfloat16) has been put in the
  program's place on the same arrivals (the upper reading).

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _brief(checks: dict) -> dict:
    return {k: {f: c[f] for f in ("value", "limit", "gap", "spread", "frames")} for k, c in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    import jax.numpy as jnp

    from bench import archs, check, harness, spec as spec_mod
    from repro.launch import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; nothing was run", file=sys.stderr)
        return 2
    compile_cache.enable()
    spec = spec_mod.load(args.workload)
    cfg = spec["config"]
    cell = harness.Cell(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = harness.measure(cell, spec, seed, args.seconds, release=False)
        pools = archs.pools(cell.models, seed)
        sched = rec["arrivals"]
        check.control_outputs(cell.models, cell.weights, pools, sched, cell.model_of,
                              jnp.dtype(cfg["precision"]["control"]))
        control = check.compare(cfg, cell.models, cell.weights, pools, sched, cell.model_of)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "due": len(sched),
            "dropped": sum(a.decision == "drop" for a in sched),
            "program": {"correct": check.correct(rec["checks"]), **_brief(rec["checks"])},
            "control": {"correct": check.correct(control), **_brief(control)},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
