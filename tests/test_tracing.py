"""The span recorder inside the serving tick (``serve.tracing``), the views
read from it, the queue-wait stamps, and the segment executables' names.

Pins: the recorder off records nothing and reads no clock; spans nest with
their parents and one frame's spans share its identifier; counters, the
bounded record of long spans and the ring hold what they promise;
``TickStats.engine_wait`` is a view over the spans (checked in
``test_batching.py``); every completion's queue wait plus its service time
is its latency; segment executables are named stably and distinctly.
"""
import gc
import itertools

import jax
import jax.numpy as jnp
import pytest

from repro import core
from repro.core.graph import LayerGraph, pointwise_meta
from repro.core.pipeline import StagedModel, executable_label
from repro.core.plan_ir import make_plan_ir
from repro.models import Pix2PixConfig, Pix2PixGenerator
from repro.serve import BatchConfig, MultiStreamServer, SLOPolicy, StreamSpec
from repro.serve import tracing
from repro.serve.tracing import OFF, SpanRecorder


def _ticker(step=1.0):
    """A clock that advances ``step`` on every read."""
    c = itertools.count()
    return lambda: next(c) * step


def _raising_clock():
    raise AssertionError("the recorder read the clock while off")


# ---- the recorder -------------------------------------------------------------


def test_off_path_records_nothing_and_reads_no_clock():
    rec = SpanRecorder(clock=_raising_clock)
    for _ in range(3):
        sp = rec.span("serve.tick", frames="a/1")
        assert sp is OFF and rec.span("executor.block", engine="E0") is OFF
        with sp as inner:
            inner.note(decision="admit")
    gc.collect()
    assert rec.recent() == [] and rec.long_spans() == [] and rec.counters == {}
    assert rec.recorded == 0 and rec.summary()["long"] == []


def test_off_server_records_nothing():
    srv = _toy_server()
    srv.tracer.clock = _raising_clock
    for _ in range(2):
        srv.offer("s0", jnp.ones((1, 8)))
        srv.tick()
    _drain(srv)
    assert srv.tracer.recent() == [] and srv.tracer.counters == {}
    assert all(t.engine_wait is None for t in srv.executor.tick_stats)
    assert "spans" not in srv.report()


def test_spans_nest_and_count():
    rec = SpanRecorder(clock=_ticker(), long_s=100.0)
    rec.enable()
    try:
        with rec.span("outer", frames="a/1") as outer:
            with rec.span("inner") as a:
                pass
            with rec.span("inner") as b:
                b.note(decision="admit")
    finally:
        rec.disable()
    assert (a.parent, b.parent, outer.parent) == (outer.id, outer.id, None)
    assert (a.depth, outer.depth) == (1, 0)
    assert b.attrs == {"decision": "admit"} and outer.attrs == {"frames": "a/1"}
    # clock reads: enable's reset 0, outer 1..6, inner 2..3 and 4..5
    assert (outer.t0, outer.t1, a.dur, b.dur) == (1.0, 6.0, 1.0, 1.0)
    assert outer.self_s == 3.0
    assert rec.counters["inner"] == [2, 2.0, 2.0, 1.0]
    assert rec.counters["outer"] == [1, 5.0, 3.0, 5.0]
    assert [s.name for s in rec.recent()] == ["inner", "inner", "outer"]


def test_long_spans_are_kept_with_their_ancestors_in_a_bounded_record():
    rec = SpanRecorder(clock=_ticker(), long_s=2.5, long_keep=2)
    rec.enable()
    try:
        for k in range(3):
            with rec.span("serve.tick", n=k):
                with rec.span("executor.resolve"):
                    with rec.span("executor.block"):
                        rec.clock(), rec.clock()  # three ticks: long
                with rec.span("serve.fold"):  # one tick: short
                    pass
    finally:
        rec.disable()
    kept = rec.long_spans()
    assert [s.name for s in kept] == ["executor.block", "executor.resolve", "serve.tick"] * 2
    assert [s.attrs for s in kept if s.name == "serve.tick"] == [{"n": 1}, {"n": 2}]
    ids = {s.id for s in kept}
    assert all(s.parent in ids for s in kept if s.depth)
    assert [d["name"] for d in rec.summary()["long"]] == [s.name for s in kept]
    assert rec.summary()["long_dropped"] == 1


def test_ring_holds_the_most_recent_spans():
    rec = SpanRecorder(clock=_ticker(), ring=4)
    rec.enable()
    try:
        for k in range(6):
            with rec.span("s", k=k):
                pass
        mark = rec.recorded
        with rec.span("s", k=6):
            pass
    finally:
        rec.disable()
    assert [s.attrs["k"] for s in rec.recent()] == [3, 4, 5, 6]
    assert [s.attrs["k"] for s in rec.since(mark)] == [6]
    assert [s.attrs["k"] for s in rec.since(0)] == [3, 4, 5, 6]


def test_garbage_collections_are_spans_with_their_generation():
    rec = SpanRecorder()
    rec.enable()
    try:
        with rec.span("serve.tick") as tick:
            gc.collect()
    finally:
        rec.disable()
    assert tracing._on_gc not in gc.callbacks
    gcs = [s for s in rec.recent() if s.name == "python.gc"]
    assert gcs and gcs[-1].attrs == {"generation": 2} and gcs[-1].parent == tick.id
    assert tick.child_s >= gcs[-1].dur


# ---- spans of the serving tick --------------------------------------------------


def _toy_model(n_layers=4):
    ops = [(f"mul{i}", lambda p, s: {"x": s["x"] * 1.5 + 0.5}) for i in range(n_layers)]
    graph = LayerGraph(
        "toy", [pointwise_meta(i, f"mul{i}", "act", (1, 8)) for i in range(n_layers)]
    ).renumber()
    return StagedModel(name="toy", ops=ops, params=None, graph=graph,
                       init_state=lambda x: {"x": x}, finalize=lambda s: s["x"],
                       batch_independent=True)


def _toy_server(batching=None, slo=None):
    plan = make_plan_ir(("toy",), ("E0", "E1"), [[(0, 0, 2), (1, 2, 4)]])
    streams = [StreamSpec(f"s{i}", 0, slo=slo) for i in range(3)]
    return MultiStreamServer([_toy_model()], plan, streams, max_queue=8, merge_batches=True,
                             batching=batching, jit_segments=False)


def _drain(srv, max_ticks=100):
    for _ in range(max_ticks):
        if not srv.executor.pending:
            return
        srv.tick()
    raise AssertionError("server did not drain")


def test_spans_nest_in_the_tick_and_one_frames_spans_share_its_identifier():
    srv = _toy_server(batching=BatchConfig(max_batch=2))
    srv.tracer.enable()
    try:
        for k in range(3):
            for s in ("s0", "s1", "s2"):
                srv.offer(s, jnp.full((1, 8), float(k)))
            srv.tick()
        _drain(srv)
        report = srv.report()
    finally:
        srv.tracer.disable()
    spans = srv.tracer.recent()
    by_id = {s.id: s for s in spans}
    parent = {s.id: by_id[s.parent].name if s.parent else None for s in spans}
    want = {"serve.offer": {None}, "serve.tick": {None}, "serve.fold": {None},
            "executor.advance": {"serve.tick"}, "executor.admit": {"serve.tick"},
            "executor.stage_in": {"executor.admit"},
            "executor.dispatch": {"executor.advance", "executor.admit"},
            "executor.place": {"executor.dispatch"}, "executor.resolve": {"serve.tick"},
            "executor.block": {"executor.resolve"}}
    for s in spans:
        if s.name in want:
            assert parent[s.id] in want[s.name], (s.name, parent[s.id])
        assert s.t0 <= s.t1 and (not s.parent or by_id[s.parent].t0 <= s.t0 <= s.t1 <= by_id[s.parent].t1)
    assert {s.name for s in spans} >= set(want)
    ex = srv.executor
    assert len(ex.completions) == 9
    for c in ex.completions:
        key = f"{c.stream}/{c.frame_id}"
        mine = [s for s in spans if key in str(s.attrs.get("frames", "")).split(";")]
        names = [s.name for s in mine]
        assert names.count("serve.offer") == 1 and names.count("executor.stage_in") == 1
        assert names.count("executor.dispatch") == 2 and names.count("executor.resolve") == 1
        flights = {str(s.attrs["flight"]) for s in mine if s.name != "serve.offer"}
        assert len(flights) == 1, (key, flights)  # one flight carried the frame throughout
    offers = [s for s in spans if s.name == "serve.offer"]
    assert {s.attrs["decision"] for s in offers} == {"admit"} and all(s.attrs["stream"] for s in offers)
    c = srv.tracer.counters
    assert c["serve.offer"][0] == 9 and c["executor.dispatch"][0] == 2 * len(
        {s.attrs["flight"] for s in spans if s.name == "executor.resolve"})
    assert set(report["spans"]["counters"]) == set(c)


def test_reset_metrics_starts_the_recorders_window_afresh():
    srv = _toy_server()
    srv.tracer.enable()
    try:
        srv.offer("s0", jnp.ones((1, 8)))
        _drain(srv)
        assert srv.tracer.counters
        srv.reset_metrics()
        assert srv.tracer.counters == {} and srv.tracer.recent() == []
    finally:
        srv.tracer.disable()


# ---- a fleet's recorders ------------------------------------------------------------


def _merge_counters(recorders):
    """The benchmark's merge of the replicas' counters (``bench.harness``)."""
    import pathlib
    import sys

    root = str(pathlib.Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import harness

    return harness.merge_counters([r.summary()["counters"] for r in recorders])


def _toy_fleet(replicas=4):
    from repro.serve import FleetServer

    plan = make_plan_ir(("toy",), ("E0", "E1"), [[(0, 0, 2), (1, 2, 4)]])
    streams = [StreamSpec(f"s{i}", 0) for i in range(2 * replicas)]
    return FleetServer([_toy_model()], plan, streams, replicas=replicas, max_queue=8,
                       jit_segments=False)


def test_one_gc_span_per_collection_however_many_recorders_are_on():
    fleet = _toy_fleet()
    tracers = [s.tracer for s in fleet.servers]
    assert [t.replica for t in tracers] == [0, 1, 2, 3]
    was = gc.isenabled()
    gc.disable()  # no collection but the forced one
    for t in tracers:
        t.enable()
    try:
        assert gc.callbacks.count(tracing._on_gc) == 1
        with tracers[2].span("serve.tick") as tick:
            gc.collect()
    finally:
        for t in tracers:
            t.disable()
        if was:
            gc.enable()
    assert tracing._on_gc not in gc.callbacks
    assert _merge_counters(tracers)["python.gc"]["count"] == 1
    # the collection is recorded inside the span that was open, by its recorder
    (s,) = [s for s in tracers[2].recent() if s.name == "python.gc"]
    assert s.parent == tick.id and s.as_dict()["replica"] == 2


def test_route_spans_count_the_offers_made_while_on():
    fleet = _toy_fleet()
    tracers = [s.tracer for s in fleet.servers]
    for name in ("s0", "s1"):  # offered while off: not counted
        fleet.offer(name, jnp.ones((1, 8)))
    for t in tracers:
        t.enable()
    try:
        for k in range(3):
            for i in range(8):
                fleet.offer(f"s{i}", jnp.full((1, 8), float(k)))
            fleet.tick()
        _drain(fleet)
    finally:
        for t in tracers:
            t.disable()
    counters = _merge_counters(tracers)
    assert counters["serve.route"]["count"] == 24 and counters["serve.offer"]["count"] == 24
    # the fleet's spans go to the first replica's recorder alone
    assert all("serve.route" not in t.counters for t in tracers[1:])
    routes = [s for s in tracers[0].recent() if s.name == "serve.route"]
    assert len(routes) == 24
    for s in routes:
        assert s.attrs["replica"] == fleet.router.replica_of(s.attrs["stream"])
        assert s.as_dict()["replica"] == s.attrs["replica"]


def test_every_replica_span_carries_its_replica(tmp_path):
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench import spans as bench_spans

    fleet = _toy_fleet()
    tracers = [s.tracer for s in fleet.servers]
    for t in tracers:
        t.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(2):
            for i in range(8):
                fleet.offer(f"s{i}", jnp.full((1, 8), float(k)))
            fleet.tick()
        _drain(fleet)
    finally:
        jax.profiler.stop_trace()
        for t in tracers:
            t.disable()
    routed = {s: fleet.router.replica_of(s) for s in (f"s{i}" for i in range(8))}
    assert set(routed.values()) == {0, 1, 2, 3}
    for r, t in enumerate(tracers):
        mine = [s for s in t.recent() if s.name != "serve.route"]
        assert {s.name for s in mine} >= {"serve.offer", "serve.tick", "executor.dispatch"}
        assert all(s.as_dict()["replica"] == r for s in mine)
        assert all(routed[s.attrs["stream"]] == r for s in mine if s.name == "serve.offer")
    # in the profiler's trace too, each span names the replica whose work it was
    traced = [sp for sp in bench_spans.load(str(tmp_path))["spans"] if sp[0].startswith("serve.")]
    assert {sp[0] for sp in traced} >= {"serve.route", "serve.offer", "serve.tick"}
    for name, _, _, attrs in traced:
        assert "replica" in attrs, (name, attrs)
        if name in ("serve.route", "serve.offer"):
            assert int(attrs["replica"]) == routed[attrs["stream"]]
    assert {int(a["replica"]) for n, _, _, a in traced if n == "serve.tick"} == {0, 1, 2, 3}


# ---- queue wait -------------------------------------------------------------------


@pytest.mark.parametrize("hold_ms", [0.0, 5.0])
def test_queue_wait_plus_service_is_the_latency(hold_ms):
    """All three come from the executor's own clock stamps: submit,
    admission into a flight, completion. With a hold window the coalescer
    holds partial buckets, and the hold counts as queue wait."""
    srv = _toy_server(batching=BatchConfig(max_batch=4, hold_ms=hold_ms),
                      slo=SLOPolicy(deadline_ms=1000.0))
    for k in range(4):
        srv.offer(f"s{k % 3}", jnp.ones((1, 8)))
        srv.tick()
    _drain(srv, max_ticks=100000)
    done = srv.executor.completions
    assert len(done) == 4
    for c in done:
        assert c.t_submit <= c.t_admit <= c.t_done
        assert abs(c.queue_wait_s + c.service_s - c.latency_s) < 1e-9
    if hold_ms:
        assert any(c.held for c in done)
        assert max(c.queue_wait_s for c in done if c.held) > 0.0
    q = srv.report()["queue"]
    assert q["frames"] == 4
    assert q["wait_ms_mean"] == pytest.approx(1e3 * sum(c.queue_wait_s for c in done) / 4)


# ---- named segment executables ------------------------------------------------------


def _pix(seed=0):
    cfg = Pix2PixConfig(img_size=32, base=8, deconv_mode="cropping")
    return core.pix2pix_staged(cfg, {"generator": Pix2PixGenerator(cfg).init(jax.random.key(seed))})


def test_segment_executables_are_named_stably_and_distinctly():
    a, b = _pix(0), _pix(1)
    spans = [(0, 5), (5, a.n_layers), (0, a.n_layers)]
    names = [a.segment_name(lo, hi, impl) for lo, hi in spans for impl in ("xla", "pallas_fused")]
    assert names == [b.segment_name(lo, hi, impl) for lo, hi in spans for impl in ("xla", "pallas_fused")]
    assert len(set(names)) == len(names)
    assert names[0] == "pix2pix_cropping.0_5.xla" and executable_label("yolo v8[n]") == "yolo_v8_n"
    state = jax.eval_shape(a.init_state, jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32))
    lowered = a.jitted_segment_fn(0, 5).lower(a.params, state)
    assert lowered.as_text().startswith("module @jit_pix2pix_cropping.0_5.xla")
    hlo = lowered.compile().as_text()
    assert hlo.startswith("HloModule jit_pix2pix_cropping.0_5.xla")
    # every op runs under its own named scope
    assert 'op_name="jit(pix2pix_cropping.0_5.xla)/down0.conv/' in hlo
    assert a.jitted_segment_fn(0, 5) is a.jitted_segment_fn(0, 5)
