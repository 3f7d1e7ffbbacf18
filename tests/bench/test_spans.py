"""Tests of the reduction of the program's spans beside the device's
operations (``bench/spans.py``) and of ``bench/spantrace.py``'s reading of
one traced run, on hand-made events, on a CPU profiler trace of the serving
tick, and on a trace recorded on a TPU v5e.
"""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spans, spantrace, trace  # noqa: E402

# two chips; the benchmark loop's window is [0, 100); ticks [0, 40) and [50, 100)
HAND = {
    "ops": {"/device:TPU:0": [["%a", 10, 20, "jit_pix.0_3.xla", ""], ["%b", 60, 10, "jit_pix.3_9.xla", ""],
                              ["%c", 75, 5, "jit_copy", ""]],
            "/device:TPU:1": [["%a", 0, 100, "jit_yolo.0_5.xla", ""]]},
    "spans": [["bench.tick", 0, 40, {}], ["bench.wait", 40, 10, {}], ["bench.tick", 50, 50, {}],
              ["serve.tick", 0, 40, {}], ["executor.dispatch", 0, 12, {"frames": "a/0"}],
              ["executor.block", 20, 20, {"engine": "E0"}], ["serve.tick", 50, 45, {}],
              ["python.gc", 80, 10, {"generation": 2}]],
}


def test_reductions_on_hand_made_events():
    assert spans.window(HAND) == (0.0, 100.0)
    idle = spans.idle_by_span(HAND)
    # chip 0 idles [0,10), [30,60), [70,75), [80,100); chip 1 never
    assert idle["serve.tick"] == pytest.approx((10 + 10 + 10 + 5 + 15) / 2 / 1e9)
    assert idle["executor.block"] == pytest.approx(10 / 2 / 1e9)
    assert idle["executor.dispatch"] == pytest.approx(10 / 2 / 1e9)
    assert idle["python.gc"] == pytest.approx(10 / 2 / 1e9)
    assert idle["none"] == pytest.approx((10 + 5) / 2 / 1e9)  # [40,50) and [95,100)
    # idle in serve.tick outside executor.block: chip 0 [0,10) [50,60) [70,75) [80,95)
    assert spans.idle_host_busy_pct(HAND) == pytest.approx(100 * (10 + 10 + 5 + 15) / 2 / 100)
    mods = spans.module_time(HAND)
    assert mods["/device:TPU:0"] == {"busy_s": pytest.approx(35e-9), "modules": {
        "jit_pix.0_3.xla": pytest.approx(20e-9), "jit_pix.3_9.xla": pytest.approx(10e-9),
        "jit_copy": pytest.approx(5e-9)}}
    assert spans.top_ops(HAND, 2) == [["%a", "jit_yolo.0_5.xla", "", pytest.approx(100e-9)],
                                      ["%a", "jit_pix.0_3.xla", "", pytest.approx(20e-9)]]


def test_a_stall_goes_to_the_deepest_span_covering_most_of_it():
    ivs = [("serve.tick", 0.0, 100.0), ("executor.resolve", 10.0, 100.0), ("executor.block", 12.0, 99.0),
           ("executor.dispatch", 0.0, 9.0)]
    assert spans.owner(ivs, 0.0, 100.0) == ("executor.block", pytest.approx(0.87))
    assert spans.owner(ivs, 0.0, 10.0) == ("executor.dispatch", pytest.approx(0.9))
    assert spans.owner(ivs, 200.0, 300.0) == ("none", 0.0)


def test_spantrace_reads_leads_and_stalls_from_one_run():
    from bench.arrivals import Arrival

    prog = {"ops": {"/device:TPU:0": [["%a", 3_000_000, 1_000_000, "jit_pix.0_3.xla", ""],
                                      ["%b", 70_000_000, 1_000_000, "jit_yolo.0_5.xla", ""]]},
            "spans": [["bench.tick", 0, 80_000_000, {}], ["serve.tick", 0, 5_000_000, {}],
                      ["executor.dispatch", 1_000_000, 2_500_000, {}],
                      ["serve.tick", 10_000_000, 65_000_000, {}],
                      ["executor.block", 12_000_000, 60_000_000, {"engine": "E0"}]]}
    done = Arrival(0.1, "p0", 0)
    done.t_offer, done.latency_s = 40.5, 0.01
    rec = {"traced_s": [40.0, 42.0], "arrivals": [], "tail": [done], "seconds": 40.0,
           "report": {"queue": {"frames": 1, "wait_ms_mean": 1.5}, "spans": {
               "counters": {}, "long": [
                   {"name": "serve.tick", "start_s": 10.0, "dur_s": 0.12},
                   {"name": "executor.stage_in", "start_s": 10.001, "dur_s": 0.118},
                   {"name": "python.gc", "start_s": 10.002, "dur_s": 0.02}]}},
           "stalls": [{"at_s": 9.999, "wall_s": 0.121, "phase": "tick"},
                      {"at_s": 41.0, "wall_s": 0.2, "phase": "tick"}]}
    got = spantrace.explain(rec, {"prog": prog, "model_of": {"p0": 0}, "labels": ["pix", "yolo"]})
    assert got["per_model"]["pix"] == {"device_s": pytest.approx(1e-3), "frames": 1,
                                       "device_ms_per_frame": pytest.approx(1.0)}
    assert got["named_share_of_busy"] == pytest.approx(1.0)
    # the first tick waits 3 ms for its first op, 2 of them in dispatch
    assert got["tick_lead_ms"]["ticks"] == 2
    assert got["tick_lead_ms"]["in_span_ms_per_tick"]["executor.dispatch"] == pytest.approx(2.0 / 2)
    assert got["tail_stalls"] == [{"at_s": pytest.approx(0.01), "wall_s": pytest.approx(0.065),
                                   "owner": "executor.block", "share": pytest.approx(60 / 65)}]
    (stall,) = got["window_stalls"]  # the one inside the window
    assert (stall["owner"], stall["share"]) == ("executor.stage_in", pytest.approx(0.118 / 0.121))


def test_program_spans_and_segment_modules_on_a_cpu_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.core.graph import LayerGraph, pointwise_meta
    from repro.core.pipeline import StagedModel
    from repro.core.plan_ir import make_plan_ir
    from repro.serve import MultiStreamServer, StreamSpec

    ops = [(f"mul{i}", lambda p, s: {"x": jnp.tanh(s["x"] @ p)}) for i in range(4)]
    graph = LayerGraph("toy", [pointwise_meta(i, f"mul{i}", "act", (1, 64)) for i in range(4)]).renumber()
    model = StagedModel(name="toy net", ops=ops, params=jnp.eye(64), graph=graph,
                        init_state=lambda x: {"x": x}, finalize=lambda s: s["x"])
    plan = make_plan_ir(("toy net",), ("E0", "E1"), [[(0, 0, 2), (1, 2, 4)]])
    srv = MultiStreamServer([model], plan, [StreamSpec("s0", 0), StreamSpec("s1", 0)])
    frame = jnp.ones((1, 64))
    srv.offer("s0", frame)
    while srv.executor.pending:
        srv.tick()  # compile outside the trace
    srv.tracer.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                srv.offer(f"s{k % 2}", frame)
                while srv.executor.pending:
                    srv.tick()
    finally:
        jax.profiler.stop_trace()
        srv.tracer.disable()
    got = spans.load(str(tmp_path))
    names = {n for n, *_ in got["spans"]}
    assert {"bench.tick", "serve.offer", "serve.tick", "executor.admit", "executor.stage_in",
            "executor.dispatch", "executor.place", "executor.resolve", "executor.block",
            "serve.fold"} <= names
    dispatch = [a for n, _, _, a in got["spans"] if n == "executor.dispatch"]
    assert {(d["lo"], d["hi"]) for d in dispatch} == {(0, 2), (2, 4)}
    assert {d["frames"] for d in dispatch} == {"s0/1", "s1/0", "s0/2"}
    modules = {o[3] for ops_ in got["ops"].values() for o in ops_}
    assert {"jit_toy_net.0_2.xla", "jit_toy_net.2_4.xla"} <= modules
    assert spans.idle_by_span(got)["serve.tick"] >= 0.0


def test_reductions_on_a_chip_trace():
    """25 ms of a traced tail of ``pix2pix_bn_yolov8n.live`` on a TPU v5e,
    recorded by ``bench/spantrace.py``."""
    rec = json.loads((ROOT / "bench" / "testdata" / "program_spans_v5e_25ms.json").read_text())
    w0, w1 = spans.window(rec)
    assert (w1 - w0) / 1e9 == pytest.approx(0.027178589, abs=1e-9)
    mods = spans.module_time(rec)["/device:TPU:0"]
    assert mods["busy_s"] == pytest.approx(0.004044276, abs=1e-9)
    # the segment executables and the rest, by module, add up to the busy union
    assert sum(mods["modules"].values()) == pytest.approx(mods["busy_s"], rel=1e-9)
    assert mods["modules"]["jit_pix2pix_cropping.33_61.xla"] == pytest.approx(0.00303191, abs=1e-9)
    assert mods["modules"]["jit_pix2pix_cropping.0_33.xla"] == pytest.approx(0.000807866, abs=1e-9)
    assert mods["modules"]["jit_convert_element_type"] == pytest.approx(0.000011268, abs=1e-9)
    # the busy union is trace.reduce's over the same events
    compact = {"device": {p: [o[:3] for o in ops] for p, ops in rec["ops"].items()},
               "host": [sp[:3] for sp in rec["spans"] if sp[0] in trace.SPANS]}
    assert trace.reduce(compact)["busy_s"] == [pytest.approx(mods["busy_s"], rel=1e-9)]
    idle = spans.idle_by_span(rec)
    assert idle["serve.tick"] == pytest.approx(0.01024828, abs=1e-9)
    assert idle["executor.dispatch"] == pytest.approx(0.004518008, abs=1e-9)
    assert idle["none"] == pytest.approx(0.012773085, abs=1e-9)
    # inside serve.tick or inside no program span: every idle nanosecond once
    assert idle["serve.tick"] + idle["serve.offer"] + idle["serve.fold"] + idle["none"] == pytest.approx(
        (w1 - w0) / 1e9 - mods["busy_s"], rel=1e-9)
    assert spans.idle_host_busy_pct(rec) == pytest.approx(30.09266228, abs=1e-6)
    assert spans.top_ops(rec, 1) == [["%fusion.1 f32[1,258,258,3]", "jit_pix2pix_cropping.33_61.xla", "",
                                      pytest.approx(0.001360494, abs=1e-9)]]
