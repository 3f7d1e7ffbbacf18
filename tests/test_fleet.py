"""Fleet serving tests: DevicePool device binding, the sticky load-aware
FleetRouter, fleet/single bit-exactness, merged fleet metrics, the shared
thread-safe OnlineCost, and the 2-replica >= 1-replica goodput pin."""
import math
import threading

import jax
import numpy as np
import pytest

from repro import core
from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
from repro.core.cost_model import OnlineCost
from repro.core.engine import DevicePool, jetson_orin_engines
from repro.models import Pix2PixConfig, Pix2PixGenerator, YOLOv8, YOLOv8Config
from repro.serve import (
    FleetRouter,
    FleetServer,
    MultiStreamServer,
    StreamSpec,
    TrafficConfig,
    build_server,
)
from repro.serve.metrics import router_imbalance


@pytest.fixture(scope="module")
def engines():
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    return gpu, dla


@pytest.fixture(scope="module")
def staged_pair():
    cfg = Pix2PixConfig(img_size=32, base=8, deconv_mode="cropping")
    gen = Pix2PixGenerator(cfg)
    sm_pix = core.pix2pix_staged(cfg, {"generator": gen.init(jax.random.key(0))})
    ycfg = YOLOv8Config(img_size=32)
    ym = YOLOv8(ycfg)
    sm_yolo = core.yolo_staged(ycfg, ym.init(jax.random.key(1)))
    return sm_pix, sm_yolo


# ---- DevicePool ------------------------------------------------------------


def test_device_pool_single_device_fallback(engines):
    """On a 1-device host every replica binds the full virtual engine pair
    to that device and placement collapses to identity."""
    gpu, dla = engines
    pool = DevicePool((dla, gpu))
    assert pool.n_devices >= 1
    if pool.n_devices == 1:
        assert pool.replica_devices(0, 2) == pool.replica_devices(1, 2)
        fns = pool.place_fns(0, 2)
        tree = {"x": jax.numpy.ones((2, 2))}
        for fn in fns:
            assert fn(tree) is tree  # identity, no device_put overhead
    for r in range(3):
        assert len(pool.replica_devices(r, 3)) >= 1


def test_device_pool_discover_defaults():
    pool = DevicePool.discover()
    assert len(pool.engines) == 2
    assert [e.name for e in pool.engines] == ["DLA", "GPU"]


def test_engine_slice_binds_devices_without_changing_identity(engines):
    """Bound specs plan identically to the abstract pair: ``device`` is
    excluded from EngineSpec equality/hash, so one plan serves every
    replica slice."""
    gpu, dla = engines
    pool = DevicePool((dla, gpu))
    sliced = pool.engine_slice(0, 2)
    assert list(sliced) == [dla, gpu]
    assert all(e.device is not None for e in sliced)
    assert hash(sliced[0]) == hash(dla)
    assert dla.bound(None) == dla


def test_device_pool_validates_inputs(engines):
    gpu, dla = engines
    with pytest.raises(ValueError):
        DevicePool(())
    with pytest.raises(ValueError):
        DevicePool((dla, gpu), devices=[])


def test_fleet_replicas_keep_weights_and_outputs_on_their_devices():
    """On a 4-device host every replica's engines run on weight copies
    placed once at build time on that replica's devices, and its outputs
    stay there; at 2 replicas each replica's two engines sit on distinct
    devices. Outputs match one replica's."""
    from conftest import run_subprocess

    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.serve import build_server
x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 32, 32, 3)), jnp.float32)
outs = {}
for r in (1, 2, 4):
    b = build_server(img=32, base=8, n_pix=2, n_yolo=1, seed=0, replicas=r)
    for s in b.streams:
        assert b.server.offer(s.name, x) == "admit"
    outs[r] = b.server.drain()
    if r == 1:
        continue
    for k, srv in enumerate(b.server.servers):
        devs = set(b.server.pool.replica_devices(k, r))
        ep = srv.executor.engine_params
        per_engine = [{d for leaf in jax.tree.leaves(p) for d in leaf.devices()} for p in ep]
        assert all(d <= devs for d in per_engine), (k, per_engine, devs)
        assert len(set().union(*per_engine)) == (2 if r == 2 else 1), per_engine
        for vals in srv.executor.outputs.values():
            for v in vals:
                assert all(leaf.devices() <= devs for leaf in jax.tree.leaves(v))
    for name, got in outs[r].items():
        for g, w in zip(got, outs[1][name], strict=True):
            for gl, wl in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
                np.testing.assert_allclose(np.asarray(gl), np.asarray(wl), atol=1e-5)
print("OK")
"""
    assert run_subprocess(code, devices=4).strip().endswith("OK")


# ---- FleetRouter -----------------------------------------------------------


def test_router_seeded_determinism():
    arrivals = [f"s{i % 6}" for i in range(40)]
    results = []
    for _ in range(2):
        r = FleetRouter(3, seed=11)
        loads = [0, 0, 0]
        routed = []
        for name in arrivals:
            rep = r.route_arrival(name, loads, deadline_s=0.1)
            loads[rep] += 1
            if len(routed) % 5 == 4:  # periodic service drains the queues
                loads = [0, 0, 0]
            routed.append(rep)
        results.append((routed, dict(r.assignments), list(r.routed_frames)))
    assert results[0] == results[1]


def test_router_sticky_stream_invariant():
    r = FleetRouter(2, seed=0)
    first = r.assign("mri-0", [0, 0], deadline_s=0.05)
    # heavily favor the other replica: the stream must not move
    other_favored = [10**6, 10**6]
    other_favored[1 - first] = 0
    assert r.assign("mri-0", other_favored) == first
    assert r.replica_of("mri-0") == first


def test_router_deadline_pressure_tiebreak():
    r = FleetRouter(2, seed=0)
    a = r.assign("tight-0", [0, 0], deadline_s=0.01)
    b = r.assign("tight-1", [0, 0], deadline_s=0.01)
    assert a != b  # equal loads: accumulated pressure pushes b elsewhere


def test_router_bounded_imbalance_under_bursty_arrivals():
    """Bursts of arrivals over 8 equal-rate streams stay balanced: the
    least-loaded rule bounds max/mean routed frames well under the
    all-on-one worst case."""
    r = FleetRouter(2, seed=3)
    loads = [0, 0]
    for burst in range(10):
        for i in range(8):
            name = f"s{i}"
            for _ in range(3):  # bursty: 3 frames back-to-back per stream
                rep = r.route_arrival(name, loads, deadline_s=0.1)
                loads[rep] += 1
        loads = [0, 0]  # inter-burst drain
    assert router_imbalance(r.routed_frames) <= 1.5
    summ = r.summary()
    assert summ["streams_assigned"] == 8
    assert sum(summ["routed_frames"]) == 10 * 8 * 3


def test_router_validates_and_resets():
    with pytest.raises(ValueError):
        FleetRouter(0)
    r = FleetRouter(2, seed=0)
    r.route_arrival("a", [0, 0])
    r.reset_counts()
    assert r.routed_frames == [0, 0]
    assert r.replica_of("a") is not None  # assignments survive the reset


def test_router_imbalance_metric():
    assert router_imbalance([5, 5]) == 1.0
    assert router_imbalance([10, 0]) == 2.0
    assert router_imbalance([0, 0]) == 1.0
    assert math.isnan(router_imbalance([]))


# ---- fleet vs single executor ----------------------------------------------


def _drive_named(server, streams, frames, n_frames):
    for t in range(n_frames):
        for s in streams:
            server.offer(s.name, frames[s.name][t])
        server.tick()
    return server.drain()


def test_fleet_bit_exact_vs_single_executor(staged_pair, engines):
    """R=2 fleet outputs are bit-exact per stream vs the same seeded
    arrivals through one MultiStreamServer: sticky routing is placement
    only, never a numerics change (shared models -> same compiled
    segment executables on both paths)."""
    gpu, dla = engines
    sm_pix, sm_yolo = staged_pair
    plan = core.plan([sm_pix.graph, sm_yolo.graph], [dla, gpu])
    streams = [StreamSpec("mri-0", 0), StreamSpec("mri-1", 0), StreamSpec("det-0", 1)]
    frames = {
        s.name: [jax.random.normal(jax.random.key(10 * i + t), (1, 32, 32, 3)) for t in range(3)]
        for i, s in enumerate(streams)
    }
    fleet = FleetServer(
        [sm_pix, sm_yolo], plan, streams, replicas=2,
        pool=DevicePool((dla, gpu)), max_queue=8,
    )
    single = MultiStreamServer([sm_pix, sm_yolo], plan, streams, max_queue=8)
    fleet_outs = _drive_named(fleet, streams, frames, 3)
    single_outs = _drive_named(single, streams, frames, 3)
    for s in streams:
        assert len(fleet_outs[s.name]) == 3
        for a, b in zip(fleet_outs[s.name], single_outs[s.name]):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # every stream stuck to exactly one replica
    assert set(fleet.router.assignments) == {s.name for s in streams}


def test_fleet_report_merges_replica_metrics(staged_pair, engines):
    gpu, dla = engines
    sm_pix, sm_yolo = staged_pair
    plan = core.plan([sm_pix.graph, sm_yolo.graph], [dla, gpu])
    streams = [StreamSpec("mri-0", 0), StreamSpec("mri-1", 0), StreamSpec("det-0", 1)]
    frames = {
        s.name: [jax.random.normal(jax.random.key(7 * i + t), (1, 32, 32, 3)) for t in range(2)]
        for i, s in enumerate(streams)
    }
    fleet = FleetServer(
        [sm_pix, sm_yolo], plan, streams, replicas=2,
        pool=DevicePool((dla, gpu)), max_queue=8,
    )
    _drive_named(fleet, streams, frames, 2)
    fleet.finish()
    rep = fleet.report()
    assert rep["replicas"] == 2
    assert rep["frames"] == 6
    assert rep["frames"] == sum(r["frames"] for r in rep["per_replica"])
    assert rep["router_imbalance"] >= 1.0
    assert sum(rep["router"]["routed_frames"]) == 6
    assert rep["dispatch"] == "overlapped"


def test_fleet_closed_loop_submit_balances(staged_pair, engines):
    """Model-index submissions (closed loop) go to the least-loaded
    replica — with symmetric load both replicas end up serving frames."""
    gpu, dla = engines
    sm_pix, sm_yolo = staged_pair
    plan = core.plan([sm_pix.graph, sm_yolo.graph], [dla, gpu])
    streams = [StreamSpec("mri-0", 0), StreamSpec("det-0", 1)]
    fleet = FleetServer(
        [sm_pix, sm_yolo], plan, streams, replicas=2,
        pool=DevicePool((dla, gpu)), max_queue=8,
    )
    for t in range(4):
        fleet.submit(0, jax.random.normal(jax.random.key(t), (1, 32, 32, 3)))
        fleet.pump()
    outs = fleet.drain()
    assert sum(len(v) for v in outs.values()) == 4
    assert all(c > 0 for c in fleet.router.routed_frames)


def test_fleet_validates_replicas(staged_pair, engines):
    gpu, dla = engines
    sm_pix, sm_yolo = staged_pair
    plan = core.plan([sm_pix.graph, sm_yolo.graph], [dla, gpu])
    streams = [StreamSpec("mri-0", 0), StreamSpec("det-0", 1)]
    with pytest.raises(ValueError):
        FleetServer([sm_pix, sm_yolo], plan, streams, replicas=0)
    with pytest.raises(ValueError):
        FleetServer(
            [sm_pix, sm_yolo], plan, streams, replicas=2,
            pool=DevicePool((dla, gpu)), replanners=[None],
        )


def test_router_sticky_across_plan_hot_swap(staged_pair, engines):
    """A mid-stream ``swap_plan`` on one replica is a routing no-op: the
    swap changes where that replica's future segments run, never which
    replica owns a stream — assignments, per-stream ordering, and frame
    counts are identical before and after the swap."""
    gpu, dla = engines
    sm_pix, sm_yolo = staged_pair
    plan = core.plan([sm_pix.graph, sm_yolo.graph], [dla, gpu], max_cuts=1)
    alt = core.plan([sm_pix.graph, sm_yolo.graph], [dla, gpu], max_cuts=2)
    streams = [StreamSpec("mri-0", 0), StreamSpec("mri-1", 0), StreamSpec("det-0", 1)]
    frames = {
        s.name: [jax.random.normal(jax.random.key(13 * i + t), (1, 32, 32, 3)) for t in range(4)]
        for i, s in enumerate(streams)
    }
    fleet = FleetServer(
        [sm_pix, sm_yolo], plan, streams, replicas=2,
        pool=DevicePool((dla, gpu)), max_queue=8,
    )
    _drive_named(fleet, streams, {n: fs[:2] for n, fs in frames.items()}, 2)
    before = dict(fleet.router.assignments)
    assert set(before) == {s.name for s in streams}
    rev = fleet.servers[0].executor.swap_plan(alt)
    assert rev >= 1
    outs = _drive_named(fleet, streams, {n: fs[2:] for n, fs in frames.items()}, 2)
    assert fleet.router.assignments == before  # no stream migrated
    for s in streams:  # post-swap frames of replica 0's streams still served
        assert len(outs[s.name]) == 4
    assert fleet.report()["plan_revision"] == rev


# ---- facade + shared OnlineCost --------------------------------------------


def test_build_server_fleet_shares_one_online_cost():
    bundle = build_server(img=32, n_pix=2, n_yolo=1, replicas=2, replan=True)
    server = bundle.server
    assert isinstance(server, FleetServer)
    assert bundle.replicas == 2
    onlines = [s.replanner.online for s in server.servers]
    assert all(o is onlines[0] for o in onlines)  # one fleet-wide store
    assert bundle.replanner is server.servers[0].replanner


def test_build_server_single_replica_unchanged():
    bundle = build_server(img=32, n_pix=1, n_yolo=1, replicas=1)
    assert isinstance(bundle.server, MultiStreamServer)
    assert bundle.replicas == 1


def test_online_cost_threaded_observe_is_consistent():
    """Concurrent observes from replica executor threads never lose
    updates: the EMA store is lock-guarded."""
    oc = OnlineCost()
    n_threads, n_obs = 4, 200

    def feed(k):
        for i in range(n_obs):
            oc.observe("GPU", observed_s=2.0e-3, expected_s=1.0e-3)
            oc.scale("GPU")

    threads = [threading.Thread(target=feed, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every observation agreed on a 2x slowdown: the converged scale must
    # see exactly that, and the snapshot must be readable post-race
    assert oc.scale("GPU") == pytest.approx(2.0, rel=1e-6)
    assert "GPU" in oc.snapshot()


# ---- goodput scaling pin (nightly tier) ------------------------------------


@pytest.mark.slow
def test_fleet_2r_goodput_not_below_1r_same_load():
    """The paper's two-instance scaling claim: at the same total offered
    load (past one replica's capacity), the 2-replica fleet's
    goodput-under-SLO is at least the single replica's. Paired runs,
    up to 3 attempts: a spurious failure needs three independent losses
    on a noisy container, a real regression fails all three."""
    def run(replicas: int) -> float:
        bundle = build_server(
            img=32, n_pix=2, n_yolo=1, deadline_ms=80.0,
            traffic=TrafficConfig(process="poisson", rate_hz=60.0, seed=5),
            admission=True, replicas=replicas,
        )
        server = bundle.server
        for s in bundle.streams:  # warm compiles out of the window
            server.submit(s.model_index, bundle.frame_for(s.name, 0))
        server.drain()
        server.reset_metrics()
        return bundle.run_open_loop(1.0, max_wall_s=120.0)["goodput_fps"]

    pairs = []
    for _ in range(3):
        g1, g2 = run(1), run(2)
        pairs.append((g1, g2))
        if g2 >= g1:
            return
    raise AssertionError(
        f"2-replica goodput below single-replica in all attempts: {pairs}"
    )
