"""Deadline-aware continuous batching: bucketed executables, the
slack-driven coalescer, batch-aware costs, and the batching metrics.

The load-bearing pins:

* **Bit-exactness** — coalesced, bucket-padded batched execution produces
  outputs bit-identical to per-frame execution on the eager path; padded
  lanes are sliced off before any completion and are never observable.
* **Deadline safety** — a partial bucket only holds when every member's
  SLO slack clears the expected batched service time plus the hold
  window, so batching can never convert a meetable deadline into a miss
  (``held_then_missed`` pinned at 0).
* **batch=1 identity** — every batch-aware code path (costs, planner,
  executor) is bit-identical to the pre-batching behaviour at batch 1.
* **No starvation** — age-tiebroken admission means every same-tier
  stream completes frames under sustained 3x overload.

The ``hypothesis`` property tests are gated on availability (the suite
must pass without it); each has a deterministic seeded equivalent.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.cost_model import (
    ANALYTIC,
    MeasuredCost,
    OnlineCost,
    batch_amortization,
    segment_cost,
)
from repro.core.engine import EngineSpec, jetson_orin_engines
from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
from repro.core.graph import LayerGraph, pointwise_meta
from repro.core.pipeline import StagedModel
from repro.core.plan_ir import PlanIR, make_plan_ir
from repro.serve import (
    BatchConfig,
    MultiStreamServer,
    SLOPolicy,
    StreamExecutor,
    StreamSpec,
    TrafficConfig,
    bucket_for,
    merge_metrics,
    metrics_from_payload,
    run_open_loop,
)
from repro.serve.executor import Flight, FlightMember, split_flight
from repro.serve.metrics import ServeMetrics, TickStats, engine_wait_summary
from repro.serve.replanner import Replanner

# ---- BatchConfig -----------------------------------------------------------


def test_batch_config_buckets_and_validation():
    bc = BatchConfig(max_batch=8, hold_ms=2.0)
    assert bc.enabled and bc.buckets == (1, 2, 4, 8)
    assert [bc.bucket_for(n) for n in (1, 2, 3, 4, 5, 8, 9)] == [1, 2, 4, 4, 8, 8, 8]
    assert bc.hold_s == pytest.approx(2e-3)
    # non-power-of-two cap: the ladder still ends exactly at max_batch
    assert BatchConfig(max_batch=6).buckets == (1, 2, 4, 6)
    assert bucket_for(5, 6) == 6
    off = BatchConfig()
    assert not off.enabled and off.buckets == (1,)
    for bad in (dict(max_batch=0), dict(hold_ms=-1.0), dict(min_slack_factor=-0.1)):
        with pytest.raises(ValueError):
            BatchConfig(**bad)


def test_batch_config_dict_roundtrip():
    bc = BatchConfig(max_batch=4, hold_ms=1.5, min_slack_factor=2.0)
    assert BatchConfig.from_dict(bc.to_dict()) == bc
    assert BatchConfig.from_dict(None) == BatchConfig()


# ---- coalesced execution is bit-exact --------------------------------------


def _toy_staged(n_layers=4, name="toy"):
    ops = [(f"mul{i}", lambda p, s: {"x": s["x"] * 1.5 + 0.5}) for i in range(n_layers)]
    graph = LayerGraph(
        name, [pointwise_meta(i, f"mul{i}", "act", (1, 8)) for i in range(n_layers)]
    ).renumber()
    return StagedModel(
        name=name,
        ops=ops,
        params=None,
        graph=graph,
        init_state=lambda x: {"x": x},
        finalize=lambda s: s["x"],
        batch_independent=True,
    )


def _toy_executor(n_streams=3, max_batch=4, hold_ms=0.0, slos=None, **kw):
    sm = _toy_staged()
    routes = make_plan_ir((sm.name,), ("E0", "E1"), [[(0, 0, 2), (1, 2, 4)]])
    streams = [
        StreamSpec(f"s{i}", 0, slo=slos[i] if slos else None) for i in range(n_streams)
    ]
    ex = StreamExecutor(
        [sm],
        routes,
        streams,
        max_queue=kw.pop("max_queue", 8),
        merge_batches=True,
        batching=BatchConfig(max_batch=max_batch, hold_ms=hold_ms),
        jit_segments=kw.pop("jit_segments", False),
        **kw,
    )
    return ex, sm, streams


def test_coalesced_bucket_padded_execution_bit_exact():
    """3 streams coalesce into a padded bucket-4 flight; every output is
    bit-identical to per-frame StagedModel.run_all (pads sliced off)."""
    ex, sm, streams = _toy_executor(n_streams=3, max_batch=4)
    frames = {
        s.name: [jax.random.normal(jax.random.key(10 * i + t), (1, 8)) for t in range(2)]
        for i, s in enumerate(streams)
    }
    for t in range(2):
        for i, s in enumerate(streams):
            assert ex.submit(i, frames[s.name][t])
        ex.run_until_drained()
    outs = ex.outputs
    for s in streams:
        for f, o in zip(frames[s.name], outs[s.name]):
            np.testing.assert_array_equal(np.asarray(sm.run_all(f)), np.asarray(o))
    # the flights really coalesced across streams: each round's 3 frames
    # ride one padded bucket-4 flight with 3 valid lanes
    assert ex.completions[0].batch == 3
    assert all(c.batch == 3 for c in ex.completions)


def _split_spans(ex):
    return [s for s in ex.tracer.since(0) if s.name == "executor.split"]


def test_split_span_once_per_multi_member_or_padded_flight():
    """With the recorder on, a finished flight is split by one compiled
    call under ``executor.resolve`` exactly when it has several members or
    pad lanes: a coalesced 3-member bucket-4 flight and a lone padded
    flight each record one ``executor.split``; a single unpadded flight
    records none and hands its output over as it is."""
    ex, sm, streams = _toy_executor(n_streams=3, max_batch=4)
    ex.tracer.enable()
    try:
        for i in range(3):
            assert ex.submit(i, jnp.full((1, 8), float(i)))
        ex.run_until_drained()
        (split,) = _split_spans(ex)
        assert split.attrs["members"] == 3 and split.attrs["bucket"] == 4
        (resolve,) = [s for s in ex.tracer.since(0) if s.name == "executor.resolve"]
        assert split.parent == resolve.id

        # a lone frame in a padded bucket: lane 1 is padding
        ex.tracer.reset()
        frame = jax.random.normal(jax.random.key(3), (1, 8))
        state = {"x": jnp.concatenate([frame, jnp.zeros((1, 8))], axis=0)}
        member = FlightMember(0, 99, 1, time.perf_counter(), ex.tick_count)
        ex._complete(Flight(0, [member], sm.run_segment(state, 0, sm.n_layers), 0, (), 0,
                            valid=1, bucket=2))
        (split,) = _split_spans(ex)
        assert split.attrs["members"] == 1 and split.attrs["bucket"] == 2
        np.testing.assert_array_equal(np.asarray(ex.completions[-1].output),
                                      np.asarray(sm.run_all(frame)))

        # a single unpadded flight: no split, the finalized output itself
        ex.tracer.reset()
        assert ex.submit(0, frame)
        ex.run_until_drained()
        assert _split_spans(ex) == []
        assert ex.completions[-1].batch == 1
        np.testing.assert_array_equal(np.asarray(ex.completions[-1].output),
                                      np.asarray(sm.run_all(frame)))
    finally:
        ex.tracer.disable()


def test_split_every_leaf_of_a_dict_output_bit_exact():
    """A merged model whose ``finalize`` returns several leaves of
    different shapes (as YOLOv8n's p3/p4/p5): the split slices every leaf,
    and each member's output is bit-identical to ``run_all`` of its frame."""
    ops = [("mul0", lambda p, s: {"x": s["x"] * 1.5 + 0.5}),
           ("mul1", lambda p, s: {"x": s["x"] * 0.75 - 0.25})]
    graph = LayerGraph("heads", [pointwise_meta(i, f"mul{i}", "act", (1, 8)) for i in range(2)]
                       ).renumber()
    sm = StagedModel(
        name="heads",
        ops=ops,
        params=None,
        graph=graph,
        init_state=lambda x: {"x": x},
        finalize=lambda s: {"p3": s["x"] * 2.0,
                            "p4": s["x"].reshape(s["x"].shape[0], 4, 2),
                            "p5": s["x"][:, :2]},
        batch_independent=True,
    )
    streams = [StreamSpec(f"s{i}", 0) for i in range(3)]
    ex = StreamExecutor([sm], make_plan_ir(("heads",), ("E0", "E1"), [[(0, 0, 1), (1, 1, 2)]]),
                        streams, max_queue=8, merge_batches=True,
                        batching=BatchConfig(max_batch=4), jit_segments=False)
    frames = [jax.random.normal(jax.random.key(20 + i), (1, 8)) for i in range(3)]
    for i, f in enumerate(frames):
        assert ex.submit(i, f)
    ex.run_until_drained()
    assert [c.batch for c in ex.completions] == [3, 3, 3]
    for s, f in zip(streams, frames):
        (out,) = ex.outputs[s.name]
        want = sm.run_all(f)
        assert set(out) == set(want) == {"p3", "p4", "p5"}
        for k in want:
            assert out[k].shape == want[k].shape
            np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(want[k]))


def test_split_compiles_once_per_bucket_and_sizes():
    """Repeated flights of one (bucket, valid) shape reuse one split
    executable: ``split_flight``'s jit cache does not grow after the
    first flight."""
    ex, sm, streams = _toy_executor(n_streams=3, max_batch=4)
    split_flight.clear_cache()
    sizes = []
    for rnd in range(4):
        for i in range(3):
            assert ex.submit(i, jax.random.normal(jax.random.key(10 * rnd + i), (1, 8)))
        ex.run_until_drained()
        sizes.append(split_flight._cache_size())
    assert all(c.batch == 3 for c in ex.completions)
    assert sizes == [1, 1, 1, 1]


def test_coalescer_random_interleavings_bit_exact_seeded():
    """Deterministic equivalent of the hypothesis property: random
    per-stream frame counts over several rounds, everything bit-exact."""
    rng = np.random.default_rng(7)
    for trial in range(3):
        ex, sm, streams = _toy_executor(n_streams=4, max_batch=4, max_queue=16)
        frames = {s.name: [] for s in streams}
        for rnd in range(3):
            for i, s in enumerate(streams):
                for t in range(int(rng.integers(0, 3))):
                    f = jax.random.normal(
                        jax.random.key(1000 * trial + 100 * rnd + 10 * i + t), (1, 8)
                    )
                    if ex.submit(i, f):
                        frames[s.name].append(f)
            ex.tick()
        outs = ex.run_until_drained()
        for s in streams:
            assert len(outs[s.name]) == len(frames[s.name])
            for f, o in zip(frames[s.name], outs[s.name]):
                np.testing.assert_array_equal(np.asarray(sm.run_all(f)), np.asarray(o))


def test_property_coalescer_bit_exact():
    """Property form of the interleaving pin (skipped without hypothesis)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=20, deadline=None)
    @hyp.given(
        counts=st.lists(
            st.lists(st.integers(min_value=0, max_value=2), min_size=3, max_size=3),
            min_size=1,
            max_size=3,
        )
    )
    def run(counts):
        ex, sm, streams = _toy_executor(n_streams=3, max_batch=4, max_queue=16)
        frames = {s.name: [] for s in streams}
        for rnd, per_stream in enumerate(counts):
            for i, n in enumerate(per_stream):
                for t in range(n):
                    f = jax.random.normal(jax.random.key(100 * rnd + 10 * i + t), (1, 8))
                    if ex.submit(i, f):
                        frames[streams[i].name].append(f)
            ex.tick()
        outs = ex.run_until_drained()
        for s in streams:
            for f, o in zip(frames[s.name], outs[s.name]):
                np.testing.assert_array_equal(np.asarray(sm.run_all(f)), np.asarray(o))

    run()


def test_swap_plan_mid_stream_with_batching_stays_exact():
    """A plan hot-swap between ticks leaves in-flight batched frames on
    their admitted routes and later buckets on the new one — outputs stay
    bit-exact throughout."""
    ex, sm, streams = _toy_executor(n_streams=3, max_batch=4, max_queue=16)
    frames = {s.name: [] for s in streams}
    for i, s in enumerate(streams):
        f = jax.random.normal(jax.random.key(i), (1, 8))
        assert ex.submit(i, f)
        frames[s.name].append(f)
    ex.tick()  # bucket in flight on the old routes
    ex.swap_plan(make_plan_ir((sm.name,), ("E0", "E1"), [[(0, 0, 1), (1, 1, 4)]]))
    for i, s in enumerate(streams):
        f = jax.random.normal(jax.random.key(100 + i), (1, 8))
        assert ex.submit(i, f)
        frames[s.name].append(f)
    outs = ex.run_until_drained()
    for s in streams:
        assert len(outs[s.name]) == 2
        for f, o in zip(frames[s.name], outs[s.name]):
            np.testing.assert_array_equal(np.asarray(sm.run_all(f)), np.asarray(o))


def test_pix2pix_instance_norm_coalesces_exactly(staged_pix_instance):
    """Real model pin: instance-norm Pix2Pix streams coalesce into one
    padded bucket and stay bit-exact on the eager path."""
    sm = staged_pix_instance
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    ir = core.plan([sm.graph], [dla, gpu])
    streams = [StreamSpec(f"p{i}", 0) for i in range(3)]
    ex = StreamExecutor(
        [sm],
        ir,
        streams,
        max_queue=4,
        merge_batches=True,
        batching=BatchConfig(max_batch=4),
        jit_segments=False,
    )
    frames = {
        s.name: jax.random.normal(jax.random.key(i), (1, 32, 32, 3))
        for i, s in enumerate(streams)
    }
    for i, s in enumerate(streams):
        assert ex.submit(i, frames[s.name])
    outs = ex.run_until_drained()
    for s in streams:
        np.testing.assert_array_equal(
            np.asarray(sm.run_all(frames[s.name])), np.asarray(outs[s.name][0])
        )
    assert ex.completions[0].batch == 3  # one coalesced flight, 3 valid lanes


@pytest.fixture(scope="module")
def staged_pix_instance():
    from repro.models import Pix2PixConfig, Pix2PixGenerator

    cfg = Pix2PixConfig(img_size=32, base=8, deconv_mode="cropping", norm="instance")
    gen = Pix2PixGenerator(cfg)
    return core.pix2pix_staged(cfg, {"generator": gen.init(jax.random.key(0))})


# ---- the slack-driven hold --------------------------------------------------


def _item(age_s: float, degrade: int = 0):
    return (0, jnp.ones((1, 8)), time.perf_counter() - age_s, degrade)


def test_hold_requires_slack_above_floor():
    slos = [SLOPolicy(deadline_ms=1e6, tier=0) for _ in range(2)]
    ex, _, _ = _toy_executor(n_streams=2, max_batch=4, hold_ms=5.0, slos=slos)
    now = time.perf_counter()
    # huge deadline, fresh frame: slack clears any floor -> hold
    assert ex._should_hold(0, [(0, _item(0.0))], now)
    # tight deadline: slack below the floor (hold window alone) -> admit
    tight = [SLOPolicy(deadline_ms=3.0, tier=0) for _ in range(2)]
    ex2, _, _ = _toy_executor(n_streams=2, max_batch=4, hold_ms=5.0, slos=tight)
    assert not ex2._should_hold(0, [(0, _item(0.0))], time.perf_counter())
    # once the service EMA knows batched service costs ~8ms, a 15ms
    # deadline no longer clears 1.5*8ms + 5ms even though it clears the
    # bare window -> admit rather than risk the merge
    mid = [SLOPolicy(deadline_ms=15.0, tier=0) for _ in range(2)]
    ex3, _, _ = _toy_executor(n_streams=2, max_batch=4, hold_ms=5.0, slos=mid)
    ex3._svc_ema[(0, 1)] = 8e-3
    assert not ex3._should_hold(0, [(0, _item(0.0))], time.perf_counter())
    ex3._svc_ema[(0, 1)] = 1e-4  # cheap batched service -> slack clears -> hold
    assert ex3._should_hold(0, [(0, _item(0.0))], time.perf_counter())


def test_hold_disabled_without_window_and_for_degraded():
    slos = [SLOPolicy(deadline_ms=1e6, tier=0) for _ in range(2)]
    # hold_ms=0: pure greedy coalescing, never holds
    ex, _, _ = _toy_executor(n_streams=2, max_batch=4, hold_ms=0.0, slos=slos)
    assert not ex._should_hold(0, [(0, _item(0.0))], time.perf_counter())
    # degraded members never wait on a merge they can't join
    ex2, _, _ = _toy_executor(n_streams=2, max_batch=4, hold_ms=5.0, slos=slos)
    assert not ex2._should_hold(0, [(0, _item(0.0, degrade=1))], time.perf_counter())


def test_hold_window_expiry_admits_partial_bucket():
    slos = [SLOPolicy(deadline_ms=1e6, tier=0) for _ in range(2)]
    ex, _, _ = _toy_executor(n_streams=2, max_batch=4, hold_ms=5.0, slos=slos)
    now = time.perf_counter()
    ex._hold_since[0] = now - 6e-3  # window (5ms) expired
    assert not ex._should_hold(0, [(0, _item(0.0))], now)


def test_held_frames_coalesce_then_complete_within_deadline():
    """A held partial bucket picks up a late co-rider, admits as one
    flight, and the completions are marked held with deadlines met
    (held_then_missed stays 0 — the deadline-safety pin)."""
    slos = [SLOPolicy(deadline_ms=1e6, tier=0) for _ in range(2)]
    ex, sm, streams = _toy_executor(n_streams=2, max_batch=2, hold_ms=50.0, slos=slos)
    f0 = jax.random.normal(jax.random.key(0), (1, 8))
    assert ex.submit(0, f0)
    ex.tick()
    assert len(ex.completions) == 0  # partial bucket held, frame still queued
    assert len(ex.queues[0]) == 1
    f1 = jax.random.normal(jax.random.key(1), (1, 8))
    assert ex.submit(1, f1)
    outs = ex.run_until_drained()
    np.testing.assert_array_equal(np.asarray(sm.run_all(f0)), np.asarray(outs["s0"][0]))
    np.testing.assert_array_equal(np.asarray(sm.run_all(f1)), np.asarray(outs["s1"][0]))
    assert [c.batch for c in ex.completions] == [2, 2]
    assert all(c.held for c in ex.completions)
    m = ServeMetrics([s.name for s in streams], slos={s.name: s.slo for s in streams})
    for c in ex.completions:
        m.record(c.stream, c.latency_s, batch=c.batch, held=c.held)
    assert m.held_frames == 2 and m.held_then_missed == 0


def test_hold_window_expiry_flushes_lone_frame():
    """With no co-rider ever arriving, the held frame is admitted solo
    once the window expires — a hold can only ever cost hold_ms."""
    slos = [SLOPolicy(deadline_ms=1e6, tier=0) for _ in range(2)]
    ex, sm, _ = _toy_executor(n_streams=2, max_batch=2, hold_ms=2.0, slos=slos)
    f0 = jax.random.normal(jax.random.key(0), (1, 8))
    assert ex.submit(0, f0)
    ex.tick()
    assert len(ex.completions) == 0
    deadline = time.perf_counter() + 2.0
    while not ex.completions and time.perf_counter() < deadline:
        time.sleep(1e-3)
        ex.tick()
    ex.run_until_drained()
    assert len(ex.completions) == 1
    assert ex.completions[0].batch == 1 and ex.completions[0].held


def test_property_hold_never_violates_slack():
    """Property form (skipped without hypothesis): for random member ages
    and deadlines, _should_hold never holds a member whose slack is at or
    below the floor."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=30, deadline=None)
    @hyp.given(
        ages_ms=st.lists(st.floats(0.0, 20.0), min_size=1, max_size=4),
        deadline_ms=st.floats(1.0, 40.0),
    )
    def run(ages_ms, deadline_ms):
        slos = [SLOPolicy(deadline_ms=deadline_ms, tier=0) for _ in range(4)]
        ex, _, _ = _toy_executor(n_streams=4, max_batch=8, hold_ms=5.0, slos=slos)
        now = time.perf_counter()
        cands = [(i, _item(a * 1e-3)) for i, a in enumerate(ages_ms)]
        if ex._should_hold(0, cands, now):
            floor = ex.batching.min_slack_factor * ex.expected_service(0, 8) + ex.batching.hold_s
            for i, item in cands:
                slack = slos[i].deadline_s - (now - item[2])
                assert slack > floor

    run()


# ---- starvation regression (age tiebreak) ----------------------------------


def test_same_tier_streams_all_complete_under_overload():
    """Sustained 3x overload over 4 same-tier streams: with the age
    tiebreak no stream can lose the admission cut forever to round-robin
    phasing — every stream completes frames."""
    sm = _toy_staged()
    engines = [
        EngineSpec("E0", 1, 1.0e12, 500e9, 50e9, ()),
        EngineSpec("E1", 1, 1.0e12, 500e9, 50e9, ()),
    ]
    ir = core.plan([sm.graph], engines)
    streams = [
        StreamSpec(f"s{i}", 0, slo=SLOPolicy(deadline_ms=60.0, tier=0)) for i in range(4)
    ]
    server = MultiStreamServer(
        [sm], ir, streams, max_queue=2, jit_segments=False, resolution_flexible=True
    )
    delay = 2e-3
    server.executor.segment_delay_fn = lambda seg: delay
    # capacity ~ 1/(2 segments * delay) per frame; drive each stream at 3x
    # its fair share of that
    rate = 3.0 * (1.0 / (2 * delay)) / len(streams)
    traffic = {
        s.name: TrafficConfig(process="poisson", rate_hz=rate, seed=20 + i)
        for i, s in enumerate(streams)
    }
    run_open_loop(server, traffic, lambda name: jnp.ones((1, 8)), 1.0, max_wall_s=120.0)
    completed = {n: m.completed for n, m in server.metrics.streams.items()}
    assert all(c > 0 for c in completed.values()), completed


# ---- batch-aware costs + planner -------------------------------------------


def test_batch_amortization_curve():
    assert batch_amortization(1) == 1.0  # batch-1 costs bit-identical
    vals = [batch_amortization(b) for b in (1, 2, 4, 8, 64)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))  # monotone nonincreasing
    assert all(v > 0.75 for v in vals)  # amortizes only the fixed fraction


def test_segment_cost_batch1_identity_and_batched_cheaper():
    from repro.models import YOLOv8, YOLOv8Config

    g = YOLOv8(YOLOv8Config(img_size=32)).layer_graph()
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    c1 = segment_cost(g, 0, len(g), gpu, gpu, True)
    c1b = segment_cost(g, 0, len(g), gpu, gpu, True, batch=1)
    assert c1.elapsed == c1b.elapsed  # bit-identical, not approx
    c4 = segment_cost(g, 0, len(g), gpu, gpu, True, batch=4)
    assert c4.elapsed < c1.elapsed  # per-frame amortized


def test_plan_batch_validation_and_ir_roundtrip():
    from repro.models import YOLOv8, YOLOv8Config

    g = YOLOv8(YOLOv8Config(img_size=32)).layer_graph()
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    with pytest.raises(ValueError):
        core.plan([g], [dla, gpu], batch=0)
    with pytest.raises(ValueError):
        core.plan([g], [dla, gpu], kind="standalone", batch=4)
    p1 = core.plan([g], [dla, gpu])
    p4 = core.plan([g], [dla, gpu], batch=4)
    assert p1.batch == 1 and p4.batch == 4
    assert p4.expected_cycle < p1.expected_cycle  # amortized per-frame cycle
    rt = PlanIR.from_json(p4.to_json())
    assert rt.batch == 4


def test_online_cost_per_bucket_scale_ladder():
    online = OnlineCost(ANALYTIC)
    online.observe("GPU", 2.0, 1.0)  # engine-wide scale 2x
    online.observe("GPU|b4", 3.0, 1.0)  # bucket-4 residual 3x
    assert online.scale_for("GPU") == pytest.approx(2.0)
    assert online.scale_for("GPU", batch=4) == pytest.approx(3.0)
    # unseen bucket falls back to the engine-wide scale
    assert online.scale_for("GPU", batch=2) == pytest.approx(2.0)


def test_measured_cost_per_bucket_cache_keys():
    m = MeasuredCost()
    g = LayerGraph(
        "t", [pointwise_meta(0, "act0", "act", (1, 16, 16, 4), flops_per_elem=2.0)]
    ).renumber()
    gpu, _ = jetson_orin_engines()
    t1 = m.layer_time(g[0], gpu)
    t4 = m.layer_time(g[0], gpu, batch=4)
    assert t1 > 0 and t4 > 0
    import re

    keys = set(m._cache)
    assert any(k.endswith("|b4") for k in keys)  # per-bucket entry
    # batch-1 key keeps the legacy un-suffixed format
    assert any(not re.search(r"\|b\d+$", k) for k in keys)


# ---- metrics: occupancy ledger + wait breakdown ----------------------------


def test_metrics_batching_ledger_and_payload_roundtrip():
    m = ServeMetrics(["a", "b"], slos={"a": SLOPolicy(deadline_ms=50.0)})
    m.record("a", 0.01, batch=4, held=True)
    m.record("a", 0.01, batch=4)
    m.record("b", 0.02, batch=1)
    m.record("a", 0.09, batch=2, held=True)  # held AND missed its 50ms deadline
    assert m.batch_occupancy == {4: 2, 1: 1, 2: 1}
    assert m.mean_effective_batch() == pytest.approx((4 + 4 + 1 + 2) / 4)
    assert m.held_frames == 2 and m.held_then_missed == 1
    m.record_tick(TickStats(0, 0.01, 0.002, 3, engine_wait={"GPU": (1e-3, 2e-4, 5e-4)}))
    rt = metrics_from_payload(m.to_payload())
    assert rt.batch_occupancy == m.batch_occupancy
    assert rt.held_frames == 2 and rt.held_then_missed == 1
    assert rt.ticks[0].engine_wait == {"GPU": (1e-3, 2e-4, 5e-4)}
    rep = rt.report(1.0)
    assert rep["batching"]["occupancy"] == {"4": 2, "1": 1, "2": 1}
    assert rep["batching"]["mean_effective_batch"] == pytest.approx(2.75)
    merged = merge_metrics([m, rt])
    assert merged.batch_occupancy == {4: 4, 1: 2, 2: 2}
    assert merged.held_then_missed == 2


def test_metrics_payload_tolerates_legacy_tick_rows():
    m = ServeMetrics(["a"])
    m.record("a", 0.01)
    payload = m.to_payload()
    payload["ticks"] = [[0, 0.01, 0.0, 2]]  # pre-batching 4-element row
    rt = metrics_from_payload(payload)
    assert rt.ticks[0].engine_wait is None
    assert rt.batch_occupancy == {1: 1}


def test_engine_wait_summary_fractions():
    ticks = [
        TickStats(0, 0.010, 0.0, 2, engine_wait={"GPU": (4e-3, 1e-3, 5e-3)}),
        TickStats(1, 0.010, 0.0, 2, engine_wait={"GPU": (0.0, 0.0, 1e-2)}),
    ]
    s = engine_wait_summary(ticks)
    assert s["GPU"]["issue_s"] == pytest.approx(4e-3)
    assert s["GPU"]["resolve_s"] == pytest.approx(1.5e-2)
    total = s["GPU"]["issue_frac"] + s["GPU"]["transfer_frac"] + s["GPU"]["resolve_frac"]
    assert total == pytest.approx(1.0)


def test_executor_reports_engine_wait_breakdown():
    """With the span recorder on, every tick's per-engine wait is read off
    its dispatch, place and block spans: non-negative, and within the
    tick's wall time. With it off there is none."""
    ex, sm, streams = _toy_executor(n_streams=2, max_batch=2)
    ex.tracer.enable()
    try:
        for i in range(2):
            assert ex.submit(i, jnp.ones((1, 8)))
        ex.run_until_drained()
    finally:
        ex.tracer.disable()
    waited = [t for t in ex.tick_stats if t.engine_wait]
    assert waited, "no per-engine wait breakdown on any tick"
    assert {name for t in waited for name in t.engine_wait} == {"E0", "E1"}
    for t in waited:
        for name, w in t.engine_wait.items():
            assert len(w) == 3 and all(x >= 0.0 for x in w)
        assert sum(x for w in t.engine_wait.values() for x in w) <= t.wall_s
    n = len(ex.tick_stats)
    assert ex.submit(0, jnp.ones((1, 8)))
    ex.run_until_drained()
    assert all(t.engine_wait is None for t in ex.tick_stats[n:])


# ---- replanner batch trigger ------------------------------------------------


class _ExecutorShim:
    def __init__(self, max_batch):
        self.batching = BatchConfig(max_batch=max_batch)


def test_replanner_batch_signal_hysteresis():
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    from repro.models import YOLOv8, YOLOv8Config

    g = YOLOv8(YOLOv8Config(img_size=32)).layer_graph()
    rp = Replanner([g], [dla, gpu])
    shim = _ExecutorShim(max_batch=4)
    # matching bucket: quiet
    rp._batch_ema = 1.0
    assert rp._batch_signal(shim) is None
    # sustained shift to bucket 4: fires only after `hysteresis` ticks
    rp._batch_ema = 3.6
    fires = [rp._batch_signal(shim) for _ in range(rp.config.hysteresis)]
    assert all(f is None for f in fires[:-1])
    assert fires[-1] == {"observed_batch": 4.0, "planned_batch": 1.0}
    # batching disabled: never fires regardless of the EMA
    rp2 = Replanner([g], [dla, gpu])
    rp2._batch_ema = 3.6
    assert rp2._batch_signal(_ExecutorShim(max_batch=1)) is None


def test_replanner_plans_at_observed_bucket():
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    from repro.models import YOLOv8, YOLOv8Config

    g = YOLOv8(YOLOv8Config(img_size=32)).layer_graph()
    rp = Replanner([g], [dla, gpu])
    rp._planned_batch = 4
    plan = rp._plan(OnlineCost(ANALYTIC))
    assert plan.batch == 4
