"""The multi-stream serving subsystem: N-model planner + stream executor.

Pins the load-bearing invariants: (a) the N-model planner degenerates to
the paper's two-model HaX-CoNN schedule exactly, (b) the tick-based
executor is a pure re-orchestration — outputs bit-exact vs the monolithic
models on the eager path (``jit_segments=False``), within the fusion
tolerance on the default jitted path — and (c) bounded queues actually
bound (backpressure).

``jit_segments=True`` is the executor default: XLA fusion of a segment
may flip low-order bits vs the eager op sequence, so default-path output
pins are *tolerance* pins (the observed drift ceiling on these 32x32
models is sub-1e-3 absolute); the eager path keeps the bit-exact pins."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
from repro.core.engine import jetson_orin_engines
from repro.core.graph import LayerGraph, pointwise_meta
from repro.core.pipeline import StagedModel
from repro.core.scheduler import ModelRoute, nmodel_schedule
from repro.models import Pix2PixConfig, Pix2PixGenerator, YOLOv8, YOLOv8Config
from repro.serve import FrameQueue, MultiStreamServer, StreamExecutor, StreamSpec
from repro.serve.metrics import percentile


@pytest.fixture(scope="module")
def engines():
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    return gpu, dla


@pytest.fixture(scope="module")
def staged_pair():
    """Small executable Pix2Pix + YOLO staged models (CPU-sized)."""
    cfg = Pix2PixConfig(img_size=32, base=8, deconv_mode="cropping")
    gen = Pix2PixGenerator(cfg)
    sm_pix = core.pix2pix_staged(cfg, {"generator": gen.init(jax.random.key(0))})
    ycfg = YOLOv8Config(img_size=32)
    ym = YOLOv8(ycfg)
    sm_yolo = core.yolo_staged(ycfg, ym.init(jax.random.key(1)))
    return sm_pix, sm_yolo


# ---- planner ---------------------------------------------------------------


def test_nmodel_n2_reproduces_haxconn(engines):
    """The N=2 specialization must pick the same partitions and cycle time
    as the exact two-model search — bit-identical, not just close."""
    gpu, dla = engines
    yolo = YOLOv8(YOLOv8Config(img_size=256)).layer_graph()
    for mode in ("padded", "cropping"):
        g = Pix2PixGenerator(Pix2PixConfig(deconv_mode=mode)).layer_graph()
        for a, b in ((g, g), (g, yolo)):
            ref = core.haxconn_schedule(a, b, dla, gpu)
            plan = nmodel_schedule([a, b], [dla, gpu])
            assert plan.partitions == [ref.p_a, ref.p_b], (mode, a.model_name, b.model_name)
            assert plan.cycle_time == ref.schedule.cycle_time
            # per-engine occupancy matches the two-phase accounting too
            assert plan.engine_times["DLA"] == ref.phase["constrained"]
            assert plan.engine_times["GPU"] == ref.phase["flexible"]


def test_nmodel_three_models_schedule_is_consistent(engines):
    gpu, dla = engines
    g = Pix2PixGenerator(Pix2PixConfig(deconv_mode="cropping")).layer_graph()
    plan = nmodel_schedule([g, g, g], [dla, gpu])  # search space > exhaustive limit
    assert len(plan.partitions) == 3
    for p, route in zip(plan.partitions, plan.routes):
        assert 0 < p < len(g)
        assert route.segments[0][2] == p and route.segments[-1][2] == len(g)
    assert plan.cycle_time == pytest.approx(max(plan.engine_times.values()))
    # three concurrent instances should out-serve one standalone instance
    solo = core.standalone_schedule(g, dla, gpu)
    assert plan.schedule.aggregate_fps > 1.0 / solo.cycle_time


def test_nmodel_fixed_partitions_respected(engines):
    gpu, dla = engines
    g = Pix2PixGenerator(Pix2PixConfig(deconv_mode="cropping")).layer_graph()
    plan = nmodel_schedule([g, g], [dla, gpu], fixed=(4, 53))
    assert plan.partitions == [4, 53]
    ref = core.haxconn_schedule(g, g, dla, gpu, fixed=(4, 53))
    assert plan.cycle_time == ref.schedule.cycle_time


# ---- executor --------------------------------------------------------------


def _plan_and_streams(sm_pix, sm_yolo, engines, n_pix=2, n_yolo=1):
    gpu, dla = engines
    plan = nmodel_schedule([sm_pix.graph, sm_yolo.graph], [dla, gpu])
    streams = [StreamSpec(f"mri-{i}", 0) for i in range(n_pix)] + [
        StreamSpec(f"det-{i}", 1) for i in range(n_yolo)
    ]
    return plan, streams


def _assert_outputs_bit_exact(outs, frames, sm_pix, sm_yolo, streams):
    for s in streams:
        sm = sm_pix if s.model_index == 0 else sm_yolo
        assert len(outs[s.name]) == len(frames[s.name])
        for f, o in zip(frames[s.name], outs[s.name]):
            ref = sm.run_all(f)
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(o)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_outputs_close(outs, frames, sm_pix, sm_yolo, streams, atol=2e-3, rtol=1e-2):
    """Tolerance pin for the default jitted path: fusion reassociates f32
    reductions; sub-1e-3 abs drift is the observed ceiling on these
    32x32 models."""
    for s in streams:
        sm = sm_pix if s.model_index == 0 else sm_yolo
        assert len(outs[s.name]) == len(frames[s.name])
        for f, o in zip(frames[s.name], outs[s.name]):
            ref = sm.run_all(f)
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(o)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol, rtol=rtol)


def test_executor_bit_exact_three_streams(staged_pair, engines):
    """3 concurrent streams through the planned routes produce outputs
    bit-exact vs StagedModel.run_all, in per-stream submission order
    (eager segment path — the pure-re-orchestration pin)."""
    sm_pix, sm_yolo = staged_pair
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines)
    ex = StreamExecutor([sm_pix, sm_yolo], plan, streams, max_queue=8, jit_segments=False)
    frames = {
        s.name: [jax.random.normal(jax.random.key(10 * i + t), (1, 32, 32, 3)) for t in range(3)]
        for i, s in enumerate(streams)
    }
    for t in range(3):
        for i, s in enumerate(streams):
            assert ex.submit(i, frames[s.name][t])
    outs = ex.run_until_drained()
    _assert_outputs_bit_exact(outs, frames, sm_pix, sm_yolo, streams)
    # double buffering: interior ticks keep both engines occupied
    ticks = {}
    for e in ex.log:
        ticks.setdefault(e.tick, set()).add(e.engine)
    interior = [t for t in ticks if 0 < t < max(ticks)]
    assert interior and all(ticks[t] == {"DLA", "GPU"} for t in interior)


def test_executor_microbatch_admits_groups_and_stays_exact(staged_pair, engines):
    """microbatch=2 admits both Pix2Pix streams in one tick (one engine
    switch per group) without changing any frame's math."""
    sm_pix, sm_yolo = staged_pair
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines)
    ex = StreamExecutor(
        [sm_pix, sm_yolo], plan, streams, max_queue=8, microbatch=2, jit_segments=False
    )
    frames = {
        s.name: [jax.random.normal(jax.random.key(7 * i + t), (1, 32, 32, 3)) for t in range(2)]
        for i, s in enumerate(streams)
    }
    for t in range(2):
        for i, s in enumerate(streams):
            assert ex.submit(i, frames[s.name][t])
    outs = ex.run_until_drained()
    _assert_outputs_bit_exact(outs, frames, sm_pix, sm_yolo, streams)
    # both pix streams admitted at tick 0 (grouped), not serialized over ticks
    tick0_admissions = [e.work for e in ex.log if e.tick == 0 and e.work.endswith("#f0")]
    assert sum(w.startswith(sm_pix.name) for w in tick0_admissions) == 2


def _toy_staged(n_layers=4, scale=2.0):
    ops = [(f"mul{i}", lambda p, s: {"x": s["x"] * scale + 1.0}) for i in range(n_layers)]
    graph = LayerGraph(
        "toy", [pointwise_meta(i, f"mul{i}", "act", (1, 8)) for i in range(n_layers)]
    ).renumber()
    return StagedModel(
        name="toy",
        ops=ops,
        params=None,
        graph=graph,
        init_state=lambda x: {"x": x},
        finalize=lambda s: s["x"],
    )


def test_executor_merge_batches_elementwise_model():
    """Array-level merging is exact for batch-independent models."""
    sm = _toy_staged()
    routes = [ModelRoute("toy", 2, [(0, 0, 2), (1, 2, 4)])]
    streams = [StreamSpec("s0", 0), StreamSpec("s1", 0)]
    ex = StreamExecutor([sm], routes, streams, max_queue=4, microbatch=2, merge_batches=True)
    frames = {s.name: [jnp.full((1, 8), float(i + t)) for t in range(2)] for i, s in enumerate(streams)}
    for t in range(2):
        for i, s in enumerate(streams):
            assert ex.submit(i, frames[s.name][t])
    outs = ex.run_until_drained()
    for s in streams:
        for f, o in zip(frames[s.name], outs[s.name]):
            np.testing.assert_array_equal(np.asarray(sm.run_all(f)), np.asarray(o))
    # merged flights really ran as one group: first tick logs one segment
    # covering both streams' frames
    merged = [e for e in ex.log if e.tick == 0]
    assert len(merged) == 1 and "#f0,0" in merged[0].work


def test_backpressure_caps_queue_depth():
    sm = _toy_staged()
    routes = [ModelRoute("toy", 2, [(0, 0, 2), (1, 2, 4)])]
    ex = StreamExecutor([sm], routes, [StreamSpec("s0", 0)], max_queue=2)
    accepted = [ex.submit(0, jnp.ones((1, 8)) * t) for t in range(6)]
    assert accepted == [True, True, False, False, False, False]
    assert ex.queues[0].high_water == 2
    assert ex.queues[0].rejected == 4
    ex.tick()  # one admission frees one slot
    assert ex.submit(0, jnp.ones((1, 8)))
    assert ex.queues[0].high_water == 2  # bound never exceeded
    ex.run_until_drained()
    assert len(ex.outputs["s0"]) == 3


def test_frame_queue_contract():
    q = FrameQueue(2)
    assert q.push(1) and q.push(2) and not q.push(3)
    assert len(q) == 2 and q.full and q.rejected == 1
    assert q.pop() == 1 and not q.full
    with pytest.raises(ValueError):
        FrameQueue(0)


# ---- dispatch modes --------------------------------------------------------


def _run_executor(sm_pix, sm_yolo, plan, streams, frames, **kw):
    ex = StreamExecutor([sm_pix, sm_yolo], plan, streams, max_queue=8, **kw)
    for t in range(len(next(iter(frames.values())))):
        for i, s in enumerate(streams):
            assert ex.submit(i, frames[s.name][t])
    outs = ex.run_until_drained()
    return ex, outs


def test_overlapped_matches_serialized_bit_exact(staged_pair, engines):
    """Overlapped dispatch is a pure re-orchestration: outputs identical to
    the per-segment-synchronized path (both default to the same jitted
    segment executables, so the comparison stays bit-exact); vs the eager
    monolithic models the default path holds the fusion tolerance pin."""
    sm_pix, sm_yolo = staged_pair
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines)
    frames = {
        s.name: [jax.random.normal(jax.random.key(13 * i + t), (1, 32, 32, 3)) for t in range(3)]
        for i, s in enumerate(streams)
    }
    _, outs_ser = _run_executor(sm_pix, sm_yolo, plan, streams, frames, dispatch="serialized")
    ex_ovl, outs_ovl = _run_executor(sm_pix, sm_yolo, plan, streams, frames, dispatch="overlapped")
    for s in streams:
        for a, b in zip(outs_ser[s.name], outs_ovl[s.name]):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # vs the monolithic eager models: tolerance pin (jit default)
    _assert_outputs_close(outs_ovl, frames, sm_pix, sm_yolo, streams)
    # per-tick overlap stats were recorded and are sane
    assert len(ex_ovl.tick_stats) == ex_ovl.tick_count
    assert all(t.wall_s >= t.blocked_s >= 0 for t in ex_ovl.tick_stats)
    assert 0.0 <= ex_ovl.overlap_efficiency() <= 1.0


def test_jit_segments_default_and_eager_modes_agree(staged_pair, engines):
    """jit_segments defaults to True; the eager opt-out stays bit-exact vs
    run_all and the two paths agree within the fusion tolerance."""
    sm_pix, sm_yolo = staged_pair
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines)
    ex = StreamExecutor([sm_pix, sm_yolo], plan, streams)
    assert ex.jit_segments is True
    frames = {
        s.name: [jax.random.normal(jax.random.key(31 * i + t), (1, 32, 32, 3)) for t in range(2)]
        for i, s in enumerate(streams)
    }
    _, outs_eager = _run_executor(sm_pix, sm_yolo, plan, streams, frames, jit_segments=False)
    _assert_outputs_bit_exact(outs_eager, frames, sm_pix, sm_yolo, streams)
    _, outs_jit = _run_executor(sm_pix, sm_yolo, plan, streams, frames)
    _assert_outputs_close(outs_jit, frames, sm_pix, sm_yolo, streams)


def test_executor_rejects_unknown_dispatch(staged_pair, engines):
    sm_pix, sm_yolo = staged_pair
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines)
    with pytest.raises(ValueError):
        StreamExecutor([sm_pix, sm_yolo], plan, streams, dispatch="yolo")


def test_jit_segments_outputs_close(staged_pair, engines):
    """Fused-segment executables may differ in low-order bits (XLA fusion)
    but must stay numerically equivalent to the eager path."""
    sm_pix, sm_yolo = staged_pair
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines)
    frames = {
        s.name: [jax.random.normal(jax.random.key(29 * i + t), (1, 32, 32, 3)) for t in range(2)]
        for i, s in enumerate(streams)
    }
    _, outs_eager = _run_executor(sm_pix, sm_yolo, plan, streams, frames, jit_segments=False)
    _, outs_jit = _run_executor(sm_pix, sm_yolo, plan, streams, frames, jit_segments=True)
    for s in streams:
        for a, b in zip(outs_eager[s.name], outs_jit[s.name]):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                # fusion reassociates f32 reductions; sub-1e-3 abs drift is
                # the observed ceiling on these 32x32 models
                np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=2e-3, rtol=1e-2)


# ---- batch-independent merging --------------------------------------------


def test_merge_batches_instance_norm_pix2pix(engines):
    """Instance-norm Pix2Pix is batch-independent, so merged micro-batches
    leave every frame's outputs unchanged vs the monolithic model."""
    from repro.serve import merge_flags_for

    cfg = Pix2PixConfig(img_size=32, base=8, deconv_mode="cropping", norm="instance")
    gen = Pix2PixGenerator(cfg)
    sm_pix = core.pix2pix_staged(cfg, {"generator": gen.init(jax.random.key(0))})
    ycfg = YOLOv8Config(img_size=32)
    ym = YOLOv8(ycfg)
    sm_yolo = core.yolo_staged(ycfg, ym.init(jax.random.key(1)))
    assert merge_flags_for([sm_pix, sm_yolo]) == [True, False]
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines)
    ex = StreamExecutor(
        [sm_pix, sm_yolo],
        plan,
        streams,
        max_queue=8,
        microbatch=2,
        merge_batches=merge_flags_for([sm_pix, sm_yolo]),
    )
    frames = {
        s.name: [jax.random.normal(jax.random.key(17 * i + t), (1, 32, 32, 3)) for t in range(2)]
        for i, s in enumerate(streams)
    }
    for t in range(2):
        for i, s in enumerate(streams):
            assert ex.submit(i, frames[s.name][t])
    outs = ex.run_until_drained()
    # default jitted path: fusion tolerance pin vs the monolithic models
    for s in streams:
        sm = sm_pix if s.model_index == 0 else sm_yolo
        for f, o in zip(frames[s.name], outs[s.name]):
            for la, lb in zip(jax.tree.leaves(sm.run_all(f)), jax.tree.leaves(o)):
                np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=2e-3, rtol=1e-2)
    # the two pix streams really ran merged: a tick-0 segment covers both
    merged = [e for e in ex.log if e.tick == 0 and "#f0,0" in e.work]
    assert merged, "expected a merged two-frame flight at tick 0"


# ---- server + metrics ------------------------------------------------------


def test_server_routes_requests_and_reports(staged_pair, engines):
    sm_pix, sm_yolo = staged_pair
    plan, streams = _plan_and_streams(sm_pix, sm_yolo, engines, n_pix=3)
    server = MultiStreamServer([sm_pix, sm_yolo], plan, streams, max_queue=2)
    for t in range(6):
        server.submit(0, jax.random.normal(jax.random.key(t), (1, 32, 32, 3)))
    server.submit(1, jax.random.normal(jax.random.key(99), (1, 32, 32, 3)))
    server.drain()
    rep = server.report()
    assert rep["frames"] == 7
    assert rep["aggregate_fps"] > 0
    assert rep["latency_p50_ms"] <= rep["latency_p99_ms"]
    # least-loaded assignment spreads the pix frames over all three streams
    per_pix = [rep["per_stream"][f"mri-{i}"]["completed"] for i in range(3)]
    assert sum(per_pix) == 6 and all(c >= 1 for c in per_pix)
    assert rep["per_stream"]["det-0"]["completed"] == 1
    # queue bound held under pressure
    assert all(q.high_water <= 2 for q in server.executor.queues)


def test_percentile_nearest_rank():
    xs = [float(i) for i in range(1, 101)]
    assert percentile(xs, 50) == 50.0
    assert percentile(xs, 99) == 99.0
    assert percentile(xs, 100) == 100.0
    assert percentile([3.0], 50) == 3.0
    assert np.isnan(percentile([], 50))


def test_build_server_refuses_workers_on_tpu_before_spawning(monkeypatch):
    """A chip belongs to one process: on a TPU backend a worker fleet
    would hang on libtpu, so build_server raises before anything runs."""
    from repro.serve import build_server, facade

    def no_build(*a, **k):
        raise AssertionError("models were built before the workers check")

    monkeypatch.setattr(facade.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(facade, "_build_pix_yolo_models", no_build)
    with pytest.raises(RuntimeError, match="one process per accelerator"):
        build_server(img=32, base=8, workers=2)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir_follows_env(env_set, tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves JAX on it and
    sets no directory; unset, the cache goes to the fixed in-checkout path."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    if env_set:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        path = compile_cache.enable()
        if env_set:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert path == str(compile_cache.CHECKOUT_DIR)
            assert jax.config.jax_compilation_cache_dir == path
            assert (compile_cache.CHECKOUT_DIR.parent / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
