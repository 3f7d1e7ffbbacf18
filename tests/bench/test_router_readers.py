"""The router layer's readers (``router.route_ms_per_frame.steady``,
``device.busy_spread_pct.steady``) on hand-built traced records, on the CPU:
known values where the record has something to read, None where it has not
(an untraced run, or a program without ``serve.route`` spans).
"""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.arrivals import Arrival  # noqa: E402
from bench.run import read_metric  # noqa: E402

ROUTE = "router.route_ms_per_frame.steady"
SPREAD = "device.busy_spread_pct.steady"


def _record(counters, busy_s):
    """Four frames completed in the traced tail [40, 42) and one before it."""
    done = []
    for k in range(4):
        a = Arrival(40.0 + 0.1 * k, f"mri-{k}", 0)
        a.t_offer, a.latency_s = a.t_due, 0.005
        done.append(a)
    early = Arrival(39.0, "det-0", 0)
    early.t_offer, early.latency_s = 39.0, 0.01
    trace = None if busy_s is None else {"busy_s": busy_s, "window_s": 2.0}
    return {"traced_s": [40.0, 42.0], "arrivals": [early], "tail": done,
            "trace": trace, "spans": None if counters is None else {"counters": counters}}


def test_route_time_per_frame_reads_the_route_spans_self_time():
    counters = {"serve.route": {"count": 6, "total_s": 0.0004, "self_s": 0.0002, "max_s": 0.0001},
                "serve.offer": {"count": 6, "total_s": 0.003, "self_s": 0.003, "max_s": 0.001}}
    assert read_metric("layer_metrics", ROUTE, _record(counters, [0.1] * 4)) == pytest.approx(0.05)


@pytest.mark.parametrize("counters", [None, {"serve.offer": {"count": 6, "total_s": 0.003,
                                                             "self_s": 0.003, "max_s": 0.001}}],
                         ids=["untraced", "no_route_spans"])
def test_route_time_per_frame_is_none_with_nothing_to_read(counters):
    assert read_metric("layer_metrics", ROUTE, _record(counters, [0.1] * 4)) is None


@pytest.mark.parametrize("busy_s,want", [
    ([0.10, 0.20, 0.15, 0.15], 100.0 * 0.10 / 0.15),
    ([0.12, 0.12, 0.12, 0.12], 0.0),
    ([0.3], 0.0),
])
def test_busy_spread_is_most_less_least_over_the_mean(busy_s, want):
    assert read_metric("layer_metrics", SPREAD, _record({}, busy_s)) == pytest.approx(want)


@pytest.mark.parametrize("busy_s", [None, [], [0.0, 0.0]], ids=["untraced", "no_chip", "idle"])
def test_busy_spread_is_none_with_nothing_to_read(busy_s):
    assert read_metric("layer_metrics", SPREAD, _record({}, busy_s)) is None
