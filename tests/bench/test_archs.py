"""Tests of the benchmark's architecture modules (``bench/archs/``), the
requests per model, the traced record's device time and roofline shares,
on the CPU.

* Golden digests: the weights from a seed, each model's request pool, the
  schedules, the reference outputs at 64 px and the FLOPs per request of
  both configurations, recorded (``golden_inputs.json``) before
  Pix2Pix's and YOLOv8's parts moved into their modules, must not change.
  Digests are exact; the reference's outputs are compared by three sums
  per leaf, since the CPU's summation order follows its thread count.
* A toy architecture (``toy_box_arch.py``, beside this file and outside
  ``bench/``), whose request is an image and an integer box, enters the
  weights, the pools, the FLOPs and the comparison with no file of
  ``bench/`` naming it.
"""
from __future__ import annotations

import hashlib
import json
import math
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import archs, arrivals, check, flops, inputs, reference, roofline, spans  # noqa: E402

GOLDEN = json.loads((HERE / "golden_inputs.json").read_text())
SEED = GOLDEN["seed"]
CONFIGS = sorted(GOLDEN["configs"])


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        a = np.ascontiguousarray(np.asarray(x))
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _sums(x) -> list[float]:
    x = np.asarray(x, np.float64).ravel()
    return [float(x.sum()), float(np.sqrt((x * x).sum())), float((x * np.sin(np.arange(x.size) * 0.37)).sum())]


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _small(models):
    return [dict(m, img_size=64, **({"base": 8} if "base" in m else {})) for m in models]


# -- golden digests -------------------------------------------------------------


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_pools_and_flops_are_unchanged(name):
    gold, models = GOLDEN["configs"][name], _config(name)["models"]
    assert _digest(reference.make_weights(models, SEED)) == gold["weights"]
    assert _digest(reference.make_weights(_small(models), 2**33 + 7)) == gold["weights_64_seed_2**33+7"]
    assert [_digest(p) for p in archs.pools(models, SEED)] == gold["pools"]
    assert [flops.per_frame(m) for m in models] == gold["flops"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_outputs_at_64_px_are_unchanged(name):
    import jax
    import jax.numpy as jnp

    gold, small = GOLDEN["configs"][name]["reference_64"], _small(_config(name)["models"])
    w = reference.make_weights(small, SEED)
    pools = archs.pools(small, SEED)
    for nudge in (None, 1):
        ref = check.reference_outputs(small, w, pools, {0: set(range(8)), 1: set(range(8))}, jnp.float32,
                                      None, nudge=nudge)
        for (mi, k), out in sorted(ref.items()):
            for got, want in zip((_sums(x) for x in jax.tree.leaves(out)), gold[str(nudge)][f"{mi}.{k}"],
                                 strict=True):
                # 25 times what 1 against 8 CPU threads move them by
                assert got == pytest.approx(want, abs=1e-3 * want[1])


@pytest.mark.parametrize("cell", sorted(GOLDEN["schedules"]))
def test_schedules_are_unchanged(cell):
    from bench import spec

    sp = spec.load(cell)
    b = sp["config"]["build"]
    streams = [f"mri-{i}" for i in range(b["n_pix"])] + [f"det-{i}" for i in range(b["n_yolo"])]
    s = arrivals.schedule(sp["mix"], streams, SEED, 40.0, inputs.POOL)
    arrivals.mark_sample(s, {n: int(n.startswith("det")) for n in streams}, 96, SEED)
    key = repr([(a.t_due, a.stream, a.pool, a.sampled) for a in s]).encode()
    assert hashlib.sha256(key).hexdigest() == GOLDEN["schedules"][cell]


def test_every_configured_architecture_has_its_module():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        for m in json.loads((ROOT / c["file"]).read_text())["models"]:
            mod = archs.load(m["arch"])
            assert (ROOT / "bench" / "archs" / f"{m['arch']}.py").is_file()
            assert all(callable(getattr(mod, f)) for f in ("shapes", "forward", "flops"))


def test_models_at_one_size_share_one_default_pool():
    models = _config("pix2pix_bn_yolov8n")["models"]
    pix, yolo = archs.pools(models, SEED)
    assert pix is yolo and len(pix) == inputs.POOL


# -- a new architecture, as new files alone ----------------------------------------

TOY = {"name": "toy", "arch": "toy_box_arch", "img_size": 32, "patch": 4, "width": 8}


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.syspath_prepend(str(HERE))
    return dict(TOY)


def test_a_new_architecture_gets_weights_pools_and_flops(toy):
    pix = _small(_config("pix2pix_bn_yolov8n")["models"])[0]
    pix_w, toy_w = reference.make_weights([pix, toy], SEED)
    # the name only the toy's INIT knows is drawn by its rule, not as zeros
    table = np.asarray(toy_w["pos"]["pos_table"])
    assert table.shape == (8, 8, 8) and 0.4 < table.std() < 0.6
    assert np.asarray(toy_w["embed"]["w"]).std() == pytest.approx(math.sqrt(2 / 16), rel=0.2)
    assert np.all(np.asarray(toy_w["head"]["b"]) != 0)
    assert np.asarray(pix_w["downs"][1]["bn"]["moving_var"]).tolist() == [1.0] * 16
    (pool,) = archs.pools([toy], SEED)
    assert len(pool) == inputs.POOL and pool[0]["box"].dtype == np.int32
    assert _digest(archs.pools([toy], SEED)) == _digest(pool)
    assert flops.per_frame(toy) == 2.0 * 64 * (16 * 8 + 8)


def _toy_run(toy, seed):
    """A config, weights, pools and a compared sample of arrivals of the toy."""
    config = {"precision": {"dtype": "float32", "conv_operands": "float32"}, "limits": {"toy": 2.0}}
    weights = reference.make_weights([toy], seed)
    pools = archs.pools([toy], seed)
    sched = arrivals.schedule({"process": "poisson", "aggregate_hz": 64}, ["t0", "t1"], seed, 1.0, inputs.POOL)
    arrivals.mark_sample(sched, {"t0": 0, "t1": 0}, 24, seed)
    for a in sched:
        a.latency_s = 0.01
    return config, weights, pools, sched


def test_the_comparison_takes_a_new_architecture(toy):
    import jax.numpy as jnp

    config, weights, pools, sched = _toy_run(toy, SEED)
    model_of = {"t0": 0, "t1": 0}
    # the reference's own answers, each on its request nudged another way
    wanted = {0: {a.pool for a in sched if a.sampled}}
    own = check.reference_outputs([toy], weights, pools, wanted, jnp.float32, jnp.float32, nudge=7)
    for a in sched:
        a.output = own.get((0, a.pool))
    sound = check.compare(config, [toy], weights, pools, sched, model_of)["toy.gap_over_spread"]
    assert sound["frames"] == 24 and 0.3 < sound["value"] < 2.0, sound
    for a in sched:
        if a.output is not None:
            a.output = dict(a.output, logits=a.output["logits"] * 1.01)
    altered = check.compare(config, [toy], weights, pools, sched, model_of)["toy.gap_over_spread"]
    assert altered["value"] > 100 * altered["limit"], altered


def test_a_nudge_moves_floating_leaves_only(toy):
    (pool,) = archs.pools([toy], SEED)
    req = pool[3]
    moved = check.nudged(req, 2)
    assert np.array_equal(moved["box"], req["box"]) and moved["box"].dtype == np.int32
    rel = np.abs(moved["image"] / req["image"] - 1)
    assert 0 < rel.max() < 1e-5
    # one floating leaf takes the draw a lone array takes
    assert np.array_equal(check.nudged(req["image"], 2), moved["image"])


# -- device time per operation and roofline shares ------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_share_of_the_roofline_is_the_larger_bound_over_the_time():
    # compute-bound: 197 GFLOP take 1 ms at peak
    assert roofline.share_pct(2e-3, 197e9, 1e6, PEAKS) == pytest.approx(50.0)
    # memory-bound: 819 MB take 1 ms at peak
    assert roofline.share_pct(4e-3, 1e9, 819e6, PEAKS) == pytest.approx(25.0)
    # counts too high read over 100 %: nothing clamps them
    assert roofline.share_pct(1e-3, 2 * 197e9, 0, PEAKS) == pytest.approx(200.0)
    assert roofline.share_pct(0.0, 1e9, 1e9, PEAKS) is None


def test_a_kernels_share_from_a_traced_record(toy):
    from bench.arrivals import Arrival

    done = []
    for t in (40.2, 40.5, 41.9):
        a = Arrival(t, "t0", 0)
        a.t_offer, a.latency_s = t, 0.01
        done.append(a)
    rec = {"traced_s": [40.0, 42.0], "arrivals": [], "tail": done, "models": [toy], "model_of": {"t0": 0},
           "peaks": PEAKS, "spans": {"op_time": {
               "jit_toy.0_3.xla": {"%convolution.1 f32[1,8,8,8]": 2e-9, "%fusion.2 f32[1,8,8,8]": 5e-9},
               "jit_other.0_1.xla": {"%convolution.1 f32[1,8,8,8]": 7e-9}}}}
    match, fl, by = archs.kernels(toy)["embed"]
    assert roofline.kernel_time_s(rec, match) == pytest.approx(2e-9)
    want = 100 * max(3 * fl / PEAKS["bf16_flops_per_s"], 3 * by / PEAKS["hbm_bytes_per_s"]) / 2e-9
    assert roofline.kernel_share_pct(rec, "toy", "embed") == pytest.approx(want)
    rec["spans"]["op_time"].pop("jit_toy.0_3.xla")
    assert roofline.kernel_share_pct(rec, "toy", "embed") is None  # nothing timed: no reading, never 0


def test_the_record_of_a_chip_trace():
    """25 ms of a traced tail of ``pix2pix_bn_yolov8n.live`` on a TPU v5e."""
    prog = json.loads((ROOT / "bench" / "testdata" / "program_spans_v5e_25ms.json").read_text())
    got = spans.reduce(prog)
    assert set(got) == {"idle_by_span", "idle_host_busy_pct", "module_time", "op_time"}
    assert got["idle_host_busy_pct"] == pytest.approx(30.09266228, abs=1e-6)
    mods = got["module_time"]["/device:TPU:0"]["modules"]
    # every op's time, by module, adds up to that module's time
    for mod, ops in got["op_time"].items():
        assert sum(ops.values()) == pytest.approx(mods[mod], rel=1e-9)
    assert got["op_time"]["jit_pix2pix_cropping.33_61.xla"]["%fusion.1 f32[1,258,258,3]"] == pytest.approx(
        0.001360494, abs=1e-9)
    assert len([op for ops in got["op_time"].values() for op in ops]) > 10  # every op, not the top ten


def test_readers_of_the_traced_record():
    from bench.arrivals import Arrival
    from bench.run import read_metric

    prog = json.loads((ROOT / "bench" / "testdata" / "program_spans_v5e_25ms.json").read_text())
    done = []
    for k, stream in enumerate(["mri-0", "mri-1", "det-0", "mri-2"]):
        a = Arrival(40.0 + 0.1 * k, stream, 0)
        a.t_offer, a.latency_s = a.t_due, 0.005
        done.append(a)
    late = Arrival(39.0, "mri-3", 0)
    late.t_offer, late.latency_s = 39.0, 0.01  # completed before the tail
    rec = {"traced_s": [40.0, 42.0], "arrivals": [late], "tail": done,
           "models": [{"name": "pix2pix"}, {"name": "yolov8"}],
           "model_of": {"mri-0": 0, "mri-1": 0, "mri-2": 0, "mri-3": 0, "det-0": 1},
           "executables": {"pix2pix": "jit_pix2pix_cropping.", "yolov8": "jit_yolov8."},
           "spans": dict(spans.reduce(prog), counters={"executor.dispatch": {
               "count": 8, "total_s": 0.006, "self_s": 0.004, "max_s": 0.001}})}
    mods = rec["spans"]["module_time"]["/device:TPU:0"]["modules"]
    pix = mods["jit_pix2pix_cropping.33_61.xla"] + mods["jit_pix2pix_cropping.0_33.xla"]
    assert read_metric("layer_metrics", "device.pix2pix_ms_per_frame.steady", rec) == pytest.approx(1e3 * pix / 3)
    # no YOLOv8 executable ran in the slice: nothing to read
    assert read_metric("layer_metrics", "device.yolov8_ms_per_frame.steady", rec) is None
    assert read_metric("layer_metrics", "executor.dispatch_ms_per_frame.steady", rec) == pytest.approx(1.0)
    for k in ("steady", "overload"):
        assert read_metric("layer_metrics", f"device.idle_host_busy_pct.{k}", rec) == pytest.approx(
            30.09266228, abs=1e-6)
    rec["spans"] = None  # an untraced run
    for m in ("device.pix2pix_ms_per_frame.steady", "executor.dispatch_ms_per_frame.steady",
              "device.idle_host_busy_pct.steady"):
        assert read_metric("layer_metrics", m, rec) is None
