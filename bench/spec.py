"""What ``BENCHMARK.json`` says about one cell, with the files it names."""
from __future__ import annotations

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = pathlib.Path(__file__).resolve().parent


def load(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, its configuration and traffic mix, and the
    end-to-end and per-layer metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": json.loads((root / config["file"]).read_text()),
        "mix": json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }
