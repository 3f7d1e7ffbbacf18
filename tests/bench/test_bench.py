"""Tests of the chip benchmark's harness (``bench/``), on the CPU.

The arithmetic (arrivals, latency from due time, window counts, FLOPs,
peaks, trace reduction) is checked on hand-made inputs and on a trace
recorded on a TPU v5e. The comparison that decides ``correct`` is driven
through whole runs of both configurations at a small size, sound, with
the bfloat16 control in the program's place, and with the timed path
broken underneath; it must pass only the sound runs.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import arrivals, check, flops, harness, peaks, spec, stats, trace  # noqa: E402
from bench.run import read_metric, result, summary  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SMALL = {"img_size": 128, "img": 128, "base": 8}  # the harness tests' model size: models' and build's keys
SEED = 2**31 + 23


# -- layout -------------------------------------------------------------------


def test_every_name_in_benchmark_json_has_its_file():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"] == []
        assert set(cfg["limits"]) == {m["name"] for m in cfg["models"]}
    for w in BENCH["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in BENCH["end_to_end"]:
        assert (ROOT / "bench" / "end_to_end" / f"{m['name']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "layer_metrics" / f"{m['name']}.py").is_file()


# -- arrivals -----------------------------------------------------------------

POISSON_GOLDEN = [
    (0.076963436, "b", 4), (0.414879555, "a", 3), (0.513964047, "a", 4), (1.048371645, "b", 0),
    (1.112852625, "b", 3), (1.373273707, "a", 1), (1.708215765, "a", 3), (1.721620311, "b", 1),
]
MMPP = {"process": "mmpp", "aggregate_hz": 8, "burst_factor": 4, "mean_burst_s": 0.5, "mean_quiet_s": 2.0}
MMPP_GOLDEN_HEAD = [(0.257908276, "a", 1), (0.372511152, "a", 3), (0.821330011, "a", 0)]


def _key(s):
    return [(round(a.t_due, 9), a.stream, a.pool) for a in s]


def test_schedule_is_fixed_by_the_seed_and_matches_golden_times():
    poisson = {"process": "poisson", "aggregate_hz": 4}
    assert _key(arrivals.schedule(poisson, ["a", "b"], 2**31 + 17, 2.0, 8)) == POISSON_GOLDEN
    m = arrivals.schedule(MMPP, ["a"], 5, 2.5, 8)
    assert _key(m)[:3] == MMPP_GOLDEN_HEAD and len(m) == 20
    assert _key(arrivals.schedule(MMPP, ["a"], 5, 2.5, 8)) == _key(m)
    assert _key(arrivals.schedule(MMPP, ["a"], 6, 2.5, 8)) != _key(m)


@pytest.mark.parametrize("mix", [{"process": "poisson", "aggregate_hz": 300}, dict(MMPP, aggregate_hz=300)])
def test_every_seed_gives_the_same_amount_of_work(mix):
    counts = {len(arrivals.schedule(mix, ["a", "b", "c"], s, 10.0, 32)) for s in (1, 2, 2**31 + 5)}
    assert counts == {3000}


def test_an_unknown_traffic_process_is_refused():
    with pytest.raises(ValueError, match="unknown traffic process"):
        arrivals.schedule({"process": "no_such_process", "aggregate_hz": 4}, ["a"], 1, 1.0, 8)

# -- window accounting --------------------------------------------------------


def _arrival(t_due, t_offer, latency_s=math.nan, stream="s0", decision="admit"):
    a = arrivals.Arrival(t_due, stream, 0)
    a.t_offer, a.latency_s, a.decision = t_offer, latency_s, decision
    return a


def test_latency_runs_from_the_due_time():
    a = _arrival(1.0, 1.25, 0.05)
    assert a.latency_due_s == pytest.approx(0.30)


def _record(arr, seconds=2.0):
    return {"arrivals": arr, "seconds": seconds, "deadline_s": 0.1, "setup_s": 12.5, "chips": 1,
            "peak": 197e12, "flops": {"s0": 1e10}, "ticks": [], "trace": None,
            "report": {"admission": {"offered": len(arr), "dropped": 1}},
            "checks": {"m.gap": {"value": 1e-5, "limit": 1e-3}}}


def test_window_goodput_attempted_and_failed():
    arr = [
        _arrival(0.1, 0.1, 0.02),  # in deadline
        _arrival(0.2, 0.2, 0.12),  # late
        _arrival(0.3, 0.35, 0.06),  # lateness pushes it past 100 ms
        _arrival(0.4, 0.4, decision="drop"),  # dropped
        _arrival(0.5, 0.5),  # admitted, never completed
        _arrival(0.6, 0.6, 0.01),  # in deadline
    ]
    rec = _record(arr)
    assert read_metric("end_to_end", "goodput_fps", rec) == pytest.approx(1.0)
    assert read_metric("end_to_end", "latency_p50_ms", rec) == pytest.approx(20.0)
    assert read_metric("layer_metrics", "admission.dropped_pct", rec) == pytest.approx(100 / 6)
    spec_ = {"end_to_end": [{"name": "goodput_fps", "unit": "frames/s"},
                            {"name": "setup_s", "unit": "s"}], "per_layer": []}
    out = result(spec_, rec, False, {"platform": "tpu"})
    assert (out["attempted"], out["failed"], out["dropped"], out["correct"]) == (6, 1, 1, True)
    assert out["metrics"]["setup_s"]["value"] == 12.5
    assert list(out)[-1] == "checks"


def test_stall_time_counts_only_the_window():
    rec = _record([], seconds=2.0)
    rec["stalls"] = [{"at_s": 0.5, "wall_s": 0.12}, {"at_s": 1.9, "wall_s": 0.11}, {"at_s": 2.1, "wall_s": 0.3}]
    assert read_metric("layer_metrics", "executor.stall_ms_per_s", rec) == pytest.approx(115.0)
    rec["stalls"] = []
    assert read_metric("layer_metrics", "executor.stall_ms_per_s", rec) == 0.0


def test_percentile_is_nearest_rank():
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.percentile([], 95) is None


# -- FLOPs and peaks ----------------------------------------------------------

PIX = {"arch": "pix2pix_unet", "img_size": 256, "base": 64, "in_channels": 3, "out_channels": 3}
YOLO = {"arch": "yolov8", "img_size": 256, "width": 0.25, "depth": 0.33, "reg_max": 16, "n_classes": 2}


def test_pix2pix_flops_per_frame():
    # products landing in the kept output; the repo's graph (12.11 GFLOP)
    # also counts the products on the transposed convs' cropped borders and
    # 0.01 GFLOP of norms and activations
    assert flops.per_frame(PIX) == pytest.approx(11.634e9, rel=1e-3)
    downs = [min(512, 64 * 2**i) for i in range(8)]
    h, border, c = 1, 0.0, downs[-1]
    for ch in downs[:-1][::-1] + [3]:
        border += 2 * (16 * h * h - (4 * h - 2) ** 2) * c * ch
        h, c = 2 * h, 2 * ch
    assert flops.per_frame(PIX) + border == pytest.approx(12.0964e9, rel=1e-4)


def test_yolov8n_flops_match_ultralytics():
    # Ultralytics reports 8.7 GFLOPs for YOLOv8n at 640 px, 80 classes
    assert flops.per_frame(dict(YOLO, img_size=640, n_classes=80)) == pytest.approx(8.7e9, rel=0.01)
    assert flops.per_frame(YOLO) == pytest.approx(1.293e9, rel=1e-3)


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


# -- trace reduction ----------------------------------------------------------


def test_trace_reduction_on_hand_made_events():
    t = {"device": {"/device:TPU:0": [["a", 10, 20], ["b", 25, 10], ["a", 60, 10]],
                    "/device:TPU:1": [["a", 0, 100]]},
         "host": [["bench.tick", 0, 40], ["bench.wait", 40, 30], ["bench.tick", 70, 30]]}
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx([35e-9, 100e-9])
    assert r["idle_share"] == pytest.approx(1 - 67.5 / 100)
    assert r["idle_gaps"] == [["bench.tick", pytest.approx(30e-9)], ["bench.wait", pytest.approx(25e-9)],
                              ["bench.tick", pytest.approx(10e-9)]]
    assert r["device_ops"][0] == ["a", pytest.approx(130e-9)]


# -- the run and the comparison that decides correct ----------------------------


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", BENCH["workloads"][0]["name"],
                        "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


@pytest.fixture(scope="module")
def cells():
    """Cells built once per module at ``SMALL`` size on the CPU."""
    built = {}

    def get(workload):
        if workload not in built:
            sp = spec.load(workload)
            # the CPU's default precision keeps conv operands in float32; and
            # with admission off the CPU's overload fills the coalescer's
            # buckets instead of shedding frames to unbatched routes
            sp["config"]["precision"]["conv_operands"] = "float32"
            sp["config"]["build"]["admission"] = False
            built[workload] = (sp, harness.Cell(sp["config"], SMALL))
        return built[workload]

    return get


def _wrap_finalize(cell, fn):
    m = cell.bundle.models[0]
    orig = m.finalize
    m.finalize = lambda s: fn(orig(s))
    return lambda: setattr(m, "finalize", orig)


def _altered(y):
    """An answer altered where it is produced: every output 1% off."""
    return y * 1.01



def _half_batch(y):
    """Half of a merged batch left out: its lanes carry the other half's answers."""
    import jax.numpy as jnp

    n = y.shape[0]
    return y if n == 1 else jnp.concatenate([y[: n - n // 2], y[: n // 2]])


def _control(cell, sched, pools):
    import jax.numpy as jnp

    check.control_outputs(cell.models, cell.weights, pools, sched, cell.model_of, jnp.bfloat16)


CASES = [
    ("pix2pix_in_yolov8n_b8.burst", "sound", True),
    ("pix2pix_in_yolov8n_b8.burst", "control", False),
    ("pix2pix_in_yolov8n_b8.burst", "altered", False),
    ("pix2pix_in_yolov8n_b8.burst", "half_batch", False),
    ("pix2pix_bn_yolov8n.live", "sound", True),
    ("pix2pix_bn_yolov8n.live", "altered", False),
]


@pytest.mark.parametrize("workload,case,want", CASES)
def test_correct_holds_only_for_the_sound_program(cells, workload, case, want):
    sp, cell = cells(workload)
    restore = _wrap_finalize(cell, {"altered": _altered, "half_batch": _half_batch}.get(case, lambda y: y))
    try:
        rec = harness.measure(cell, sp, SEED, 1.0, after_window=_control if case == "control" else None,
                              release=False)
    finally:
        restore()
    assert all(c["frames"] > 0 for c in rec["checks"].values()), rec["checks"]
    if case == "half_batch":
        assert any(a.batch > 1 and a.output is not None for a in rec["arrivals"])
    if case == "sound":
        json.dumps(summary(rec))  # the lines printed before the result hold for a real run's record
    assert check.correct(rec["checks"]) is want, rec["checks"]


@pytest.fixture(scope="module")
def fleet():
    """The four-replica configuration (not yet a cell: PERF.md section 7),
    its replicas on the one CPU device, at ``SMALL`` size."""
    config = json.loads((ROOT / "bench" / "configs" / "pix2pix_bn_yolov8n_r4.json").read_text())
    config["precision"]["conv_operands"] = "float32"
    config["build"]["admission"] = False
    sp = {"cell": {"chips": 1}, "config": config, "mix": {"process": "poisson", "aggregate_hz": 200}}
    return sp, harness.Cell(config, SMALL)


@pytest.mark.parametrize("case,want", [("sound", True), ("altered", False)])
def test_correct_holds_only_for_the_sound_fleet(fleet, case, want):
    sp, cell = fleet
    assert len(cell.executors) == len(cell.tracers) == 4 and len(cell.streams) == 20
    restore = _wrap_finalize(cell, _altered if case == "altered" else lambda y: y)
    try:
        rec = harness.measure(cell, sp, SEED, 1.0, release=False)
    finally:
        restore()
    assert all(c["frames"] > 0 for c in rec["checks"].values()), rec["checks"]
    assert check.correct(rec["checks"]) is want, rec["checks"]


def test_span_counters_of_replicas_add_up():
    a = {"executor.dispatch": {"count": 2, "total_s": 0.5, "self_s": 0.25, "max_s": 0.3}}
    b = {"executor.dispatch": {"count": 1, "total_s": 0.25, "self_s": 0.125, "max_s": 0.25},
         "serve.tick": {"count": 1, "total_s": 1.0, "self_s": 0.5, "max_s": 1.0}}
    assert harness.merge_counters([a, b]) == {
        "executor.dispatch": {"count": 3, "total_s": 0.75, "self_s": 0.375, "max_s": 0.3},
        "serve.tick": {"count": 1, "total_s": 1.0, "self_s": 0.5, "max_s": 1.0}}


def test_trace_reduction_on_a_chip_trace():
    rec = json.loads((ROOT / "bench" / "testdata" / "trace_v5e_50ms.json").read_text())
    r = trace.reduce(rec)
    assert r["window_s"] == pytest.approx(0.050796595, abs=1e-9)
    assert r["busy_s"] == [pytest.approx(0.005154253, abs=1e-9)]
    assert r["idle_share"] == pytest.approx(1 - 0.005154253 / 0.050796595, rel=1e-9)
    # every idle nanosecond is attributed to one of the driver's spans
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"][0], rel=1e-9)
    assert r["idle_by_span_s"] == {"bench.tick": pytest.approx(0.042345473, abs=1e-9),
                                   "bench.wait": pytest.approx(0.003296869, abs=1e-9)}
    assert r["idle_gaps"][0] == ["bench.tick", pytest.approx(0.010529371, abs=1e-9)]
    assert r["device_ops"][0] == ["%fusion.1 f32[1,258,258,3]", pytest.approx(0.001700613, abs=1e-9)]


def test_the_traced_tail_turns_the_program_recorders_on(cells, tmp_path):
    """The window runs with the span recorders off; the tail after it with
    them on, and the window returns their counters over the tail alone."""
    from bench import archs, inputs, spans
    from bench.stats import model_device_ms_per_frame

    sp, cell = cells("pix2pix_bn_yolov8n.live")
    pools = archs.pools(cell.models, SEED)
    cell.load_weights(SEED)
    cell.warm_up(pools)
    mix = {"process": "poisson", "aggregate_hz": 20}
    sched = arrivals.schedule(mix, cell.streams, SEED, 0.5, inputs.POOL)
    tail = arrivals.schedule(mix, cell.streams, SEED, harness.TRACE_S, inputs.POOL, salt=1)
    for a in tail:
        a.t_due += 0.5
    timing = cell.window(sched + tail, pools, 0.5, str(tmp_path))
    assert "spans" not in timing["report"]  # the window's counters, recorder off
    assert not any(tr.enabled for tr in cell.tracers)
    counters = timing["span_counters"]
    assert counters["serve.tick"]["count"] > 0
    assert 0 < counters["executor.dispatch"]["self_s"] <= counters["executor.dispatch"]["total_s"]
    # every tail frame was offered inside the traced tail, none of the window's
    assert counters["serve.offer"]["count"] == len(tail)
    prog = spans.load(str(tmp_path))
    rec = {"traced_s": timing["traced_s"], "arrivals": sched, "tail": tail, "models": cell.models,
           "model_of": cell.model_of, "executables": cell.executables,
           "spans": dict(spans.reduce(prog), counters=counters)}
    assert {n for n, *_ in prog["spans"]} >= {"serve.tick", "executor.dispatch", "bench.tick"}
    assert model_device_ms_per_frame(rec, "pix2pix") > 0 and model_device_ms_per_frame(rec, "yolov8") > 0
