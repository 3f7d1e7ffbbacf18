"""Multi-stream serving benchmark: aggregate FPS and latency percentiles
vs concurrent stream count, the coarse-vs-fine planning-granularity
comparison (composite vs expanded primitive cut points: plan cost and
measured FPS), the replicated-fleet scaling sweep (goodput vs replica
count behind the sticky load-aware router), plus the online re-planning
perturbation-recovery scenario, written to ``BENCH_serve.json`` so
successive PRs have a perf trajectory to compare against
(``benchmarks/trend.py`` diffs two runs and gates CI on regressions).

  PYTHONPATH=src python benchmarks/serve_bench.py --smoke
  PYTHONPATH=src python benchmarks/serve_bench.py --streams 1,2,4,8 --frames 16
  PYTHONPATH=src python benchmarks/serve_bench.py --cost measured --norm instance
  PYTHONPATH=src python benchmarks/serve_bench.py --smoke --skew 4

Each run serves K Pix2Pix reconstruction streams plus one YOLOv8
detection stream through the planned ``StreamExecutor`` on CPU; absolute
numbers are container-dependent, the *shape* (FPS vs K, tail latency
growth, overlapped-vs-serialized dispatch gap, recovery ratio) is the
tracked signal. The planner runs under the ``--cost`` provider (analytic
roofline by default, XLA-measured per-layer costs with ``--cost
measured``); the JSON records which provider and search mode produced
every plan.

The **perturbation-recovery scenario** calibrates an attached
``Replanner``, injects a ``--skew``x cost skew on the engine carrying the
most movable work (a host-side stall proportional to each segment's
calibrated wall time — a thermally throttled engine looks exactly like
this), and tracks per-window FPS while the drift detector fires and
hot-swaps re-planned routes in. Recorded: the recovery curve, the swap
events, a zero-dropped-frames check, and an output-equality check vs an
unperturbed run on the final plan from the start (within the jitted
fusion tolerance).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import socket
import time


def build_models(img: int, base: int, norm: str, provider, search: str, impl: str = "xla"):
    """Build the staged models + plan once per bench process: every point
    reuses them, so jitted segment executables (cached on the models)
    compile once during warmup instead of once per point."""
    from repro.serve import build_pix_yolo_serving

    models, plan, _, _ = build_pix_yolo_serving(
        img=img, base=base, n_pix=1, n_yolo=1, norm=norm, cost=provider, search=search, impl=impl
    )
    return models, plan


def run_point(
    models,
    plan,
    n_pix_streams: int,
    frames_per_stream: int,
    img: int,
    microbatch: int,
    norm: str = "batch",
    dispatch: str = "overlapped",
    jit_segments: bool = True,
) -> dict:
    import jax

    from repro.serve import MultiStreamServer, StreamSpec, merge_flags_for

    streams = [StreamSpec(f"mri-{i}", 0) for i in range(n_pix_streams)] + [StreamSpec("det-0", 1)]
    server = MultiStreamServer(
        models,
        plan,
        streams,
        max_queue=4,
        microbatch=microbatch,
        merge_batches=merge_flags_for(models),
        dispatch=dispatch,
        jit_segments=jit_segments,
    )

    t0 = time.perf_counter()
    for t in range(frames_per_stream):
        for s in streams:
            server.submit(s.model_index, jax.random.normal(jax.random.key(t), (1, img, img, 3)))
        server.pump()
    server.drain()
    wall = time.perf_counter() - t0
    rep = server.report()
    return {
        "pix_streams": n_pix_streams,
        "yolo_streams": 1,
        "streams": len(streams),
        "frames": rep["frames"],
        "wall_s": wall,
        "aggregate_fps": rep["frames"] / wall,
        "latency_p50_ms": rep["latency_p50_ms"],
        "latency_p99_ms": rep["latency_p99_ms"],
        "overlap_efficiency": rep["overlap"]["overlap_efficiency"],
        "dispatch": dispatch,
        "norm": norm,
        "merge_batches": merge_flags_for(models),
        "cost_provider": plan.cost_provider,
        "planner_search": plan.search,
        "planned_cycle_ms": plan.cycle_time * 1e3,
        "planned_partitions": plan.partitions,
    }


def run_granularity_compare(
    img: int, base: int, norm: str, frames: int, microbatch: int, stride: int = 1
) -> dict:
    """Coarse-vs-fine planning granularity on the YOLO+Pix2Pix pair.

    Plans the same model pair at composite-node granularity and at
    expanded (primitive, stage-callable-legal) granularity, re-scores the
    coarse plan's cut points on the expanded graphs so the analytic costs
    are like-for-like, and measures end-to-end FPS for both through the
    executor. At ``stride=1`` (the recorded default) the fine planner
    searches a superset of the coarse cut points, so its analytic cost is
    never worse; ``stride > 1`` thins the fine candidate set (it may drop
    the coarse boundaries), so the ratio then measures what the
    tractability knob costs, not the never-worse guarantee."""
    from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
    from repro.core.engine import jetson_orin_engines
    from repro.core.scheduler import _nmodel_schedule_impl as nmodel_schedule
    from repro.serve import build_pix_yolo_serving

    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    models_c, plan_c, _, _ = build_pix_yolo_serving(img=img, base=base, n_pix=1, n_yolo=1, norm=norm)
    models_f, plan_f, _, _ = build_pix_yolo_serving(
        img=img, base=base, n_pix=1, n_yolo=1, norm=norm, granularity="fine", stride=stride
    )
    fine_graphs = [m.graph for m in models_f]
    coarse_on_fine = nmodel_schedule(
        fine_graphs,
        [dla, gpu],
        fixed=tuple(g.fine_cut(p) for g, p in zip(fine_graphs, plan_c.partitions)),
    )
    # warm both stacks, then measure interleaved medians (container drift
    # between a single coarse run and a single fine run easily exceeds the
    # granularity effect)
    k = 2
    for models, plan in ((models_c, plan_c), (models_f, plan_f)):
        run_point(models, plan, k, 1, img, microbatch, norm)
    cs, fs = [], []
    for _ in range(3):
        cs.append(run_point(models_c, plan_c, k, frames, img, microbatch, norm))
        fs.append(run_point(models_f, plan_f, k, frames, img, microbatch, norm))
    r_coarse = sorted(cs, key=lambda r: r["aggregate_fps"])[len(cs) // 2]
    r_fine = sorted(fs, key=lambda r: r["aggregate_fps"])[len(fs) // 2]
    out = {
        "stride": stride,
        "repeats": 3,
        "coarse_partitions": plan_c.partitions,
        "fine_partitions": plan_f.partitions,
        "fine_coarse_spans": [
            [[s.lo, s.hi, s.coarse_lo, s.coarse_hi] for s in segs] for segs in plan_f.ir.segments
        ],
        "coarse_plan_cycle_ms": plan_c.cycle_time * 1e3,
        "coarse_plan_cycle_ms_rescored_fine": coarse_on_fine.cycle_time * 1e3,
        "fine_plan_cycle_ms": plan_f.cycle_time * 1e3,
        "plan_cost_ratio": plan_f.cycle_time / coarse_on_fine.cycle_time,
        "coarse_fps": r_coarse["aggregate_fps"],
        "fine_fps": r_fine["aggregate_fps"],
        "fps_ratio": r_fine["aggregate_fps"] / r_coarse["aggregate_fps"],
        "coarse_latency_p50_ms": r_coarse["latency_p50_ms"],
        "fine_latency_p50_ms": r_fine["latency_p50_ms"],
    }
    return out


def run_multicut_compare(
    img: int, base: int, norm: str, frames: int, microbatch: int, cuts_list=(1, 2, 3)
) -> dict:
    """``max_cuts`` sweep on the Pix2Pix + YOLO serving pair.

    Plans the same model pair at each cut budget and records the analytic
    plan cost next to measured end-to-end FPS through the executor
    (interleaved medians — container drift between back-to-back runs
    easily exceeds the routing effect). The single-cut candidates are a
    subset of every higher budget's and the planner polishes the best
    single-cut vector inside the multi-cut space, so the analytic cycle
    is never worse as ``max_cuts`` grows — the recorded ratios measure
    how much of that headroom the executor realizes."""
    from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
    from repro.core.engine import jetson_orin_engines
    from repro.core.scheduler import _nmodel_schedule_impl as nmodel_schedule
    from repro.serve import build_pix_yolo_serving

    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    models, _, _, _ = build_pix_yolo_serving(img=img, base=base, n_pix=1, n_yolo=1, norm=norm)
    graphs = [m.graph for m in models]
    plans = {mc: nmodel_schedule(graphs, [dla, gpu], max_cuts=mc) for mc in cuts_list}

    k = 2
    for plan in plans.values():  # warm every plan's segment executables
        run_point(models, plan, k, 1, img, microbatch, norm)
    samples: dict[int, list[dict]] = {mc: [] for mc in cuts_list}
    for _ in range(3):
        for mc in cuts_list:
            samples[mc].append(run_point(models, plans[mc], k, frames, img, microbatch, norm))
    med = {
        mc: sorted(rs, key=lambda r: r["aggregate_fps"])[len(rs) // 2]
        for mc, rs in samples.items()
    }
    base_mc = cuts_list[0]
    points = {
        str(mc): {
            "plan_cycle_ms": plans[mc].cycle_time * 1e3,
            "cuts": [list(c) for c in plans[mc].cuts],
            "planner_search": plans[mc].search,
            "aggregate_fps": med[mc]["aggregate_fps"],
            "latency_p50_ms": med[mc]["latency_p50_ms"],
        }
        for mc in cuts_list
    }
    best_mc = max(cuts_list, key=lambda mc: med[mc]["aggregate_fps"])
    # the analytic ratio is keyed to the analytically-best budget — it
    # records the planner's headroom (>= 1.0 by the never-worse
    # guarantee) independently of which budget noisy measured FPS favors
    analytic_best = min(cuts_list, key=lambda mc: plans[mc].cycle_time)
    return {
        "max_cuts": list(cuts_list),
        "repeats": 3,
        "pix_streams": k,
        "points": points,
        "best_max_cuts": best_mc,
        "analytic_best_max_cuts": analytic_best,
        "plan_cost_ratio": plans[base_mc].cycle_time / plans[analytic_best].cycle_time,
        # measured ratio stays keyed to the FPS-best budget (container
        # jitter can put it at 1 cut even when the analytic plan is
        # cheaper — per-segment host dispatch is not free on CPU)
        "fps_ratio": med[best_mc]["aggregate_fps"] / med[base_mc]["aggregate_fps"],
    }


def run_impl_compare(
    img: int, base: int, norm: str, frames: int, microbatch: int, impls=("xla", "auto", "pallas")
) -> dict:
    """Implementation-planning sweep on the Pix2Pix + YOLO serving pair.

    Plans the same model pair under each ``--impl`` mode with *measured*
    per-layer costs (the fused-kernel win is a measured effect; analytic
    roofline cycles for the same three modes ride along), records each
    plan's cycle and per-segment implementation bindings, and measures
    end-to-end FPS through the executor — ``pallas_fused`` segments stage
    the fused serving kernels, so the FPS numbers exercise the real
    variant dispatch, not just the plan annotation. ``auto`` picks the
    per-segment argmin over both variants and only switches when the
    candidate dominates component-wise, so its plan cycle is never worse
    than forced ``xla`` (the recorded ratio is the pinned guarantee).
    Interpreted Pallas on CPU makes the absolute ``pallas``/``auto``
    wall-clock non-indicative; the plan-cost columns carry the signal."""
    from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
    from repro.core.cost_model import MeasuredCost
    from repro.core.engine import jetson_orin_engines
    from repro.core.scheduler import _nmodel_schedule_impl as nmodel_schedule
    from repro.serve import build_pix_yolo_serving

    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    models, _, _, _ = build_pix_yolo_serving(img=img, base=base, n_pix=1, n_yolo=1, norm=norm)
    graphs = [m.graph for m in models]
    mc = MeasuredCost()
    plans = {im: nmodel_schedule(graphs, [dla, gpu], provider=mc, impl=im) for im in impls}
    analytic = {im: nmodel_schedule(graphs, [dla, gpu], impl=im) for im in impls}

    k = 2
    cmp_frames = min(frames, 6)  # interpreted Pallas is slow on CPU; keep it bounded
    for plan in plans.values():  # warm every plan's segment executables
        run_point(models, plan, k, 1, img, microbatch, norm)
    samples: dict[str, list[dict]] = {im: [] for im in impls}
    for _ in range(3):  # interleaved repeats cancel container drift
        for im in impls:
            samples[im].append(run_point(models, plans[im], k, cmp_frames, img, microbatch, norm))
    med = {
        im: sorted(rs, key=lambda r: r["aggregate_fps"])[len(rs) // 2]
        for im, rs in samples.items()
    }
    points = {
        im: {
            "plan_cycle_ms": plans[im].cycle_time * 1e3,
            "analytic_plan_cycle_ms": analytic[im].cycle_time * 1e3,
            "impl_bindings": [list(b) for b in plans[im].ir.impl_bindings()],
            "pallas_segments": sum(
                1 for b in plans[im].ir.impl_bindings() for s in b if s == "pallas_fused"
            ),
            "aggregate_fps": med[im]["aggregate_fps"],
            "latency_p50_ms": med[im]["latency_p50_ms"],
        }
        for im in impls
    }
    return {
        "impls": list(impls),
        "repeats": 3,
        "pix_streams": k,
        "frames_per_stream": cmp_frames,
        "cost_provider": "measured",
        "points": points,
        "auto_vs_xla_plan_ratio": plans["auto"].cycle_time / plans["xla"].cycle_time,
        "auto_vs_xla_analytic_ratio": analytic["auto"].cycle_time / analytic["xla"].cycle_time,
        "auto_never_worse": plans["auto"].cycle_time <= plans["xla"].cycle_time
        and analytic["auto"].cycle_time <= analytic["xla"].cycle_time,
    }


def run_openloop_sweep(
    img: int,
    base: int,
    norm: str,
    microbatch: int,
    load_factors=(0.5, 1.0, 3.0),
    horizon_s: float = 1.5,
    n_pix: int = 2,
    max_queue: int = 4,
    queue_only_depth: int = 64,
) -> dict:
    """Open-loop scenario sweep: offered load at fractions/multiples of the
    measured closed-loop capacity, under a deadline SLO with the
    graceful-degradation admission controller on.

    The SLO deadline is derived from the measured capacity — 1.2x the
    worst bounded backlog in frame-service-times — so the contract under
    test is load-geometry, not a container-speed constant: with bounded
    queues every admitted frame can make its deadline, while the 3x
    *queue-only baseline* (admission off, ``queue_only_depth`` queues)
    backlogs far past it and collapses goodput. Recorded per point:
    goodput-under-SLO (total and per tier), p50/p99, and the
    admit/shed/drop ledger; plus the 3x shed-vs-queue-only goodput ratio
    and p99 comparison the trend gate and tests pin."""
    import dataclasses

    import jax

    from repro.serve import (
        AdmissionConfig,
        MultiStreamServer,
        SLOPolicy,
        StreamSpec,
        TrafficConfig,
        build_pix_yolo_serving,
        merge_flags_for,
        run_open_loop,
    )

    models, plan, streams, _ = build_pix_yolo_serving(
        img=img, base=base, n_pix=n_pix, n_yolo=1, norm=norm
    )

    def frame(si: int, t: int):
        return jax.random.normal(jax.random.key(1000 * si + t), (1, img, img, 3))

    def make_server(slo_streams, admission, depth):
        server = MultiStreamServer(
            models,
            plan,
            slo_streams,
            max_queue=depth,
            microbatch=microbatch,
            merge_batches=merge_flags_for(models),
            admission=admission,
        )
        for t in range(2):  # warm compiled segments before measuring
            for si, s in enumerate(slo_streams):
                server.submit(s.model_index, frame(si, t))
            server.pump()
        server.drain()
        # also warm the degraded paths the admission ladder can route to
        # mid-measurement: level-1 frames fly solo (unmerged shapes) and
        # level-2 frames run the single-segment degraded route — both
        # compile on first use, and a multi-second XLA compile inside the
        # measured window would masquerade as an SLO collapse
        for level in (1, 2):
            for si in range(len(slo_streams)):
                server.executor.submit(si, frame(si, 50 + level), degrade=level)
            server.executor.run_until_drained()
        server.reset_metrics()
        return server

    # closed-loop capacity of the warmed stack = the 1x reference rate
    cal = make_server(streams, None, max_queue)
    n_cal = 6
    t0 = time.perf_counter()
    for t in range(n_cal):
        for si, s in enumerate(streams):
            cal.submit(s.model_index, frame(si, 100 + t))
        cal.pump()
    cal.drain()
    capacity = n_cal * len(streams) / (time.perf_counter() - t0)

    # deadline: 1.2x the worst bounded backlog, in frame-service-times —
    # feasible under bounded queues, infeasible under the deep baseline
    deadline_ms = 1.2 * max_queue * len(streams) / capacity * 1e3
    slo_streams = [
        dataclasses.replace(
            s,
            slo=SLOPolicy(
                deadline_ms=deadline_ms,
                tier=0 if s.model_index == 1 else 1,  # detection outranks reconstruction
                name=f"{s.name}-slo",
            ),
        )
        for s in streams
    ]

    def drive(server, factor: float, seed0: int) -> dict:
        rate = factor * capacity / len(streams)
        traffic = {
            s.name: TrafficConfig(process="poisson", rate_hz=rate, seed=seed0 + i)
            for i, s in enumerate(slo_streams)
        }
        counts: dict[str, int] = {}

        def frame_fn(name: str):
            t = counts.get(name, 0)
            counts[name] = t + 1
            si = next(i for i, s in enumerate(slo_streams) if s.name == name)
            return frame(si, 10_000 + t)

        rep = run_open_loop(server, traffic, frame_fn, horizon_s, max_wall_s=600.0)
        adm = rep["admission"]
        return {
            "load_factor": factor,
            "offered_rate_hz": rate * len(slo_streams),
            "offered": adm["offered"],
            "admitted": adm["admitted"],
            "shed_res": adm["shed_res"],
            "shed_route": adm["shed_route"],
            "dropped": adm["dropped"],
            "aggregate_fps": rep["aggregate_fps"],
            "goodput_fps": rep["goodput_fps"],
            "latency_p50_ms": rep["latency_p50_ms"],
            "latency_p99_ms": rep["latency_p99_ms"],
            "slo_miss_rate_recent": rep["slo_miss_rate_recent"],
            "tiers": {
                t: {
                    "offered": tm["offered"],
                    "goodput_fps": tm["goodput_fps"],
                    "slo_attainment": tm["slo_attainment"],
                }
                for t, tm in rep["tiers"].items()
            },
        }

    points = {}
    for i, f in enumerate(load_factors):
        server = make_server(slo_streams, AdmissionConfig(), max_queue)
        points[str(f)] = drive(server, f, seed0=10 * (i + 1))
    top = max(load_factors)
    # the 3x queue-only baseline: same arrivals, no admission control,
    # queues deep enough to absorb the whole burst — throughput survives,
    # goodput collapses (every queued frame blows its deadline)
    queue_only = drive(
        make_server(slo_streams, None, queue_only_depth), top, seed0=10 * (len(load_factors) + 1)
    )
    shed_top = points[str(top)]
    q_good = queue_only["goodput_fps"]
    return {
        "process": "poisson",
        "streams": len(slo_streams),
        "horizon_s": horizon_s,
        "capacity_fps": capacity,
        "deadline_ms": deadline_ms,
        "max_queue": max_queue,
        "queue_only_depth": queue_only_depth,
        "load_factors": list(load_factors),
        "points": points,
        "queue_only_top": queue_only,
        "shed_vs_queue_goodput_ratio": shed_top["goodput_fps"] / q_good if q_good > 0 else float("inf"),
        "p99_bounded_at_top": shed_top["latency_p99_ms"] <= queue_only["latency_p99_ms"],
    }


def run_batching_sweep(
    img: int,
    base: int,
    microbatch: int,
    max_batches=(1, 4, 8),
    load_factors=(1.0, 3.0),
    horizon_s: float = 1.0,
    n_pix: int = 4,
    max_queue: int = 8,
    hold_ms: float = 2.0,
) -> dict:
    """Continuous-batching sweep: goodput vs ``max_batch`` at 1x and 3x
    offered load.

    Serves ``n_pix`` instance-norm Pix2Pix streams (batch-independent, so
    the cross-stream coalescer is live) plus one YOLO stream under a
    deadline SLO, at each coalescer cap. At 1x load slack is plentiful
    and the slack-driven hold assembles full buckets; at 3x the queues
    are deep enough that buckets fill greedily without holding. Recorded
    per point: goodput, latency percentiles, mean effective batch, the
    bucket-occupancy histogram, and the held-frame ledger. The trend-gated
    contract is ``batched_vs_unbatched_goodput_ratio_3x >= 1.0`` (the
    best batched cap's goodput at top load vs ``max_batch=1``, absolute)
    and ``held_then_missed == 0`` everywhere — the slack gate means a
    hold can never turn a meetable deadline into a miss."""
    import dataclasses

    import jax

    from repro.serve import (
        BatchConfig,
        MultiStreamServer,
        SLOPolicy,
        StreamSpec,
        TrafficConfig,
        build_pix_yolo_serving,
        merge_flags_for,
        run_open_loop,
    )

    # instance norm: per-sample statistics, so coalesced batches are exact
    # and merge_flags_for marks the pix model batch-independent
    models, plan, streams, _ = build_pix_yolo_serving(
        img=img, base=base, n_pix=n_pix, n_yolo=1, norm="instance"
    )

    def frame(si: int, t: int):
        return jax.random.normal(jax.random.key(1000 * si + t), (1, img, img, 3))

    def make_server(slo_streams, bc: BatchConfig | None):
        server = MultiStreamServer(
            models,
            plan,
            slo_streams,
            max_queue=max_queue,
            microbatch=microbatch,
            merge_batches=merge_flags_for(models),
            batching=bc,
        )
        # warm every bucket executable the coalescer can reach: a
        # multi-second XLA compile inside the measured window would read
        # as an SLO collapse
        buckets = bc.buckets if bc is not None else (1,)
        for b in buckets:
            for _ in range(b):
                for si, s in enumerate(slo_streams):
                    server.submit(s.model_index, frame(si, 50 + b))
            server.pump()
            server.drain()
        server.reset_metrics()
        return server

    # closed-loop capacity of the unbatched stack = the 1x reference rate
    cal = make_server(streams, None)
    n_cal = 6
    t0 = time.perf_counter()
    for t in range(n_cal):
        for si, s in enumerate(streams):
            cal.submit(s.model_index, frame(si, 100 + t))
        cal.pump()
    cal.drain()
    capacity = n_cal * len(streams) / (time.perf_counter() - t0)

    deadline_ms = 1.2 * max_queue * len(streams) / capacity * 1e3
    slo_streams = [
        dataclasses.replace(
            s,
            slo=SLOPolicy(
                deadline_ms=deadline_ms,
                tier=0 if s.model_index == 1 else 1,
                name=f"{s.name}-slo",
            ),
        )
        for s in streams
    ]

    def drive(server, factor: float, seed0: int) -> dict:
        rate = factor * capacity / len(slo_streams)
        traffic = {
            s.name: TrafficConfig(process="poisson", rate_hz=rate, seed=seed0 + i)
            for i, s in enumerate(slo_streams)
        }
        counts: dict[str, int] = {}

        def frame_fn(name: str):
            t = counts.get(name, 0)
            counts[name] = t + 1
            si = next(i for i, s in enumerate(slo_streams) if s.name == name)
            return frame(si, 10_000 + t)

        rep = run_open_loop(server, traffic, frame_fn, horizon_s, max_wall_s=600.0)
        bat = rep["batching"]
        return {
            "load_factor": factor,
            "offered_rate_hz": rate * len(slo_streams),
            "frames": rep["frames"],
            "aggregate_fps": rep["aggregate_fps"],
            "goodput_fps": rep["goodput_fps"],
            "latency_p50_ms": rep["latency_p50_ms"],
            "latency_p99_ms": rep["latency_p99_ms"],
            "mean_effective_batch": bat["mean_effective_batch"],
            "occupancy": bat["occupancy"],
            "held_frames": bat["held_frames"],
            "held_then_missed": bat["held_then_missed"],
        }

    points: dict[str, dict] = {}
    for i, mb in enumerate(max_batches):
        bc = BatchConfig(max_batch=mb, hold_ms=hold_ms) if mb > 1 else None
        per_load = {}
        for j, f in enumerate(load_factors):
            server = make_server(slo_streams, bc)
            per_load[str(f)] = drive(server, f, seed0=100 * (i + 1) + 10 * (j + 1))
        points[str(mb)] = per_load

    top = str(max(load_factors))
    unbatched_top = points[str(min(max_batches))][top]
    batched_caps = [mb for mb in max_batches if mb > 1]
    best_batched = (
        max((points[str(mb)][top] for mb in batched_caps), key=lambda p: p["goodput_fps"])
        if batched_caps
        else unbatched_top
    )
    ratio = (
        best_batched["goodput_fps"] / unbatched_top["goodput_fps"]
        if unbatched_top["goodput_fps"] > 0
        else float("inf")
    )
    return {
        "max_batches": list(max_batches),
        "load_factors": list(load_factors),
        "streams": len(slo_streams),
        "norm": "instance",
        "hold_ms": hold_ms,
        "horizon_s": horizon_s,
        "capacity_fps": capacity,
        "deadline_ms": deadline_ms,
        "max_queue": max_queue,
        "points": points,
        "batched_vs_unbatched_goodput_ratio_3x": ratio,
        "held_then_missed_total": sum(
            p["held_then_missed"] for per in points.values() for p in per.values()
        ),
    }


def run_fleet_sweep(
    img: int,
    base: int,
    norm: str,
    microbatch: int,
    replica_counts=(1, 2, 4),
    horizon_s: float = 1.0,
    n_pix: int = 4,
    max_queue: int = 4,
    router_seed: int = 0,
    traffic_seed: int = 0,
) -> dict:
    """Replicated-fleet scaling sweep: goodput-under-SLO vs replica count.

    Two experiments through the same ``build_server`` facade the CLIs use.
    **Matched per-replica load**: each R-replica fleet is offered R x a
    fixed fraction of the measured single-pipeline capacity, so every
    replica sees the same per-replica pressure and the recorded
    ``scaling_efficiency`` (goodput(R) / (R x goodput(1))) isolates how
    much of the replication the fleet realizes — overlapping executors
    keep more async segment executions in flight, which is real
    parallelism even on a 1-device CPU host. **Same total load**: R=1 vs
    R=2 under an *identical* seeded arrival sequence at ~2x the single
    pipeline's capacity — the overloaded single replica sheds/misses
    where the fleet has headroom, so ``same_load_goodput_ratio_2v1`` is
    the paper's two-instance scaling claim as one number (>= 1.0 is the
    trend-gated contract). Router imbalance rides along per point."""
    from repro.serve import TrafficConfig, build_server

    n_streams = n_pix + 1

    def build(replicas: int, rate_per_stream: float, deadline_ms: float, seed0: int):
        bundle = build_server(
            img=img, base=base, n_pix=n_pix, n_yolo=1, norm=norm,
            microbatch=microbatch, max_queue=max_queue,
            deadline_ms=deadline_ms,
            traffic=TrafficConfig(process="poisson", rate_hz=rate_per_stream, seed=seed0),
            admission=True, replicas=replicas, router_seed=router_seed,
        )
        server = bundle.server
        for t in range(2):  # warm compiled segments before measuring
            for s in bundle.streams:
                server.submit(s.model_index, bundle.frame_for(s.name, t))
            server.pump()
        server.drain()
        server.reset_metrics()
        return bundle

    # closed-loop capacity of one warmed replica = the per-replica unit load
    cal = build(1, 1.0, 100.0, traffic_seed)
    n_cal = 6
    t0 = time.perf_counter()
    for t in range(n_cal):
        for s in cal.streams:
            cal.server.submit(s.model_index, cal.frame_for(s.name, 100 + t))
        cal.server.pump()
    cal.server.drain()
    capacity = n_cal * n_streams / (time.perf_counter() - t0)
    # deadline feasible under bounded queues on ONE replica (cf. the
    # open-loop sweep) — replication can only relieve it
    deadline_ms = 1.2 * max_queue * n_streams / capacity * 1e3

    def drive(bundle) -> dict:
        rep = bundle.run_open_loop(horizon_s, max_wall_s=600.0)
        adm = rep["admission"]
        return {
            "replicas": bundle.replicas,
            "offered": adm["offered"],
            "admitted": adm["admitted"],
            "dropped": adm["dropped"],
            "frames": rep["frames"],
            "aggregate_fps": rep["aggregate_fps"],
            "goodput_fps": rep["goodput_fps"],
            "latency_p50_ms": rep["latency_p50_ms"],
            "latency_p99_ms": rep["latency_p99_ms"],
            "router_imbalance": rep.get("router_imbalance", 1.0),
            "routed_frames": rep["router"]["routed_frames"] if "router" in rep else None,
        }

    per_replica_factor = 0.6  # below capacity so scaling isn't shed-limited
    points = {}
    for i, R in enumerate(replica_counts):
        rate = per_replica_factor * R * capacity / n_streams
        p = drive(build(R, rate, deadline_ms, traffic_seed + 10 * (i + 1)))
        p["offered_rate_hz"] = rate * n_streams
        points[str(R)] = p
    base_r = min(replica_counts)
    base_good = points[str(base_r)]["goodput_fps"]
    scaling = {
        str(R): (points[str(R)]["goodput_fps"] * base_r / (R * base_good)) if base_good > 0 else 0.0
        for R in replica_counts
    }

    # same total offered load, identical seeded arrivals: 1 vs 2 replicas
    same_rate = 2.0 * capacity / n_streams
    same_seed = traffic_seed + 1000
    rep1 = drive(build(1, same_rate, deadline_ms, same_seed))
    rep2 = drive(build(2, same_rate, deadline_ms, same_seed))
    ratio = (
        rep2["goodput_fps"] / rep1["goodput_fps"] if rep1["goodput_fps"] > 0 else float("inf")
    )
    return {
        "replica_counts": list(replica_counts),
        "streams": n_streams,
        "horizon_s": horizon_s,
        "capacity_fps": capacity,
        "deadline_ms": deadline_ms,
        "per_replica_load_factor": per_replica_factor,
        "router_seed": router_seed,
        "traffic_seed": traffic_seed,
        "points": points,
        "scaling_efficiency": scaling,
        "same_load_offered_rate_hz": same_rate * n_streams,
        "same_load_1r": rep1,
        "same_load_2r": rep2,
        "same_load_goodput_ratio_2v1": ratio,
    }


def run_proc_fleet_sweep(
    img: int,
    base: int,
    norm: str,
    microbatch: int,
    worker_counts=(1, 2, 4),
    horizon_s: float = 1.0,
    n_pix: int = 4,
    max_queue: int = 4,
    router_seed: int = 0,
    traffic_seed: int = 0,
) -> dict:
    """Multi-process fleet scaling sweep: worker *processes* vs goodput.

    The process analogue of ``run_fleet_sweep``: the same two experiments
    (matched per-worker load -> ``scaling_efficiency``; same total load at
    ~2x single-worker capacity under identical seeded arrivals ->
    ``same_load_goodput_ratio_2v1``, the trend-gated >= 1.0 contract)
    through ``build_server(workers=W)`` — spawned worker processes behind
    the IPC router, shared-memory frame transport included. Capacity is
    calibrated closed-loop against a 1-worker fleet so the unit load
    already pays the RPC overhead the scaling points pay. Worker spawn +
    build cost is real (each worker re-stages and warms its models), so
    bundles are reused across drives: the W=1 and W=2 scaling bundles
    also serve the same-load comparison after a ``reset_metrics``.

    Process parallelism needs processors: on a single-core host two
    workers merely context-switch against each other and the >= 1.0
    same-load contract is physically void, so the payload records the
    schedulable core count and ``same_load_contract_applicable`` — the
    CI assertion and trend gate key off it (GitHub runners have >= 2
    cores, so the contract stays live where it means something)."""
    from repro.serve import TrafficConfig, build_server
    from repro.serve.traffic import run_open_loop

    n_streams = n_pix + 1

    def build(workers: int, deadline_ms: float):
        t0 = time.perf_counter()
        bundle = build_server(
            img=img, base=base, n_pix=n_pix, n_yolo=1, norm=norm,
            microbatch=microbatch, max_queue=max_queue,
            deadline_ms=deadline_ms,
            # placeholder process: drives pass their own traffic configs
            traffic=TrafficConfig(process="poisson", rate_hz=1.0, seed=traffic_seed),
            admission=True, workers=workers, router_seed=router_seed,
            jit_segments=True,
        )
        return bundle, time.perf_counter() - t0

    def drive(bundle, rate_per_stream: float, seed0: int) -> dict:
        # per-stream re-seeded arrivals, same idiom as the facade's
        # traffic normalization — rates vary per drive without rebuilding
        # the worker processes
        traffic = {
            s.name: TrafficConfig(process="poisson", rate_hz=rate_per_stream, seed=seed0 + si)
            for si, s in enumerate(bundle.streams)
        }
        counts: dict[str, int] = {}

        def frame_fn(name: str):
            t = counts.get(name, 0)
            counts[name] = t + 1
            return bundle.frame_for(name, t)

        bundle.server.reset_metrics()
        rep = run_open_loop(bundle.server, traffic, frame_fn, horizon_s, max_wall_s=600.0)
        adm = rep["admission"]
        return {
            "workers": bundle.workers,
            "offered": adm["offered"],
            "admitted": adm["admitted"],
            "dropped": adm["dropped"],
            "frames": rep["frames"],
            "aggregate_fps": rep["aggregate_fps"],
            "goodput_fps": rep["goodput_fps"],
            "latency_p50_ms": rep["latency_p50_ms"],
            "latency_p99_ms": rep["latency_p99_ms"],
            "router_imbalance": rep.get("router_imbalance", 1.0),
            "routed_frames": rep["router"]["routed_frames"] if "router" in rep else None,
            "worker_failures": len(rep.get("worker_failures", [])),
        }

    bundles: dict[int, tuple] = {}
    try:
        # closed-loop capacity of a 1-worker fleet (workers self-warm at
        # spawn) = the per-worker unit load, RPC overhead included
        cal, _ = build(1, 100.0)
        n_cal = 6
        t0 = time.perf_counter()
        for t in range(n_cal):
            for s in cal.streams:
                cal.server.submit(s.model_index, cal.frame_for(s.name, 100 + t))
            cal.server.pump()
        cal.server.drain()
        capacity = n_cal * n_streams / (time.perf_counter() - t0)
        cal.close()
        deadline_ms = 1.2 * max_queue * n_streams / capacity * 1e3

        per_worker_factor = 0.6
        points = {}
        for i, W in enumerate(worker_counts):
            bundles[W] = build(W, deadline_ms)
            rate = per_worker_factor * W * capacity / n_streams
            p = drive(bundles[W][0], rate, traffic_seed + 10 * (i + 1))
            p["offered_rate_hz"] = rate * n_streams
            p["startup_s"] = bundles[W][1]
            points[str(W)] = p
        base_w = min(worker_counts)
        base_good = points[str(base_w)]["goodput_fps"]
        scaling = {
            str(W): (points[str(W)]["goodput_fps"] * base_w / (W * base_good))
            if base_good > 0
            else 0.0
            for W in worker_counts
        }

        # same total offered load, identical seeded arrivals: 1 vs 2 workers
        # (reusing the warmed scaling bundles; drive() resets metrics)
        same_rate = 2.0 * capacity / n_streams
        same_seed = traffic_seed + 1000
        if 1 not in bundles:
            bundles[1] = build(1, deadline_ms)
        if 2 not in bundles:
            bundles[2] = build(2, deadline_ms)
        rep1 = drive(bundles[1][0], same_rate, same_seed)
        rep2 = drive(bundles[2][0], same_rate, same_seed)
        ratio = (
            rep2["goodput_fps"] / rep1["goodput_fps"] if rep1["goodput_fps"] > 0 else float("inf")
        )
    finally:
        for b, _ in bundles.values():
            b.close()
    cores = (
        len(os.sched_getaffinity(0))
        if hasattr(os, "sched_getaffinity")
        else (os.cpu_count() or 1)
    )
    return {
        "worker_counts": list(worker_counts),
        "streams": n_streams,
        "horizon_s": horizon_s,
        "capacity_fps": capacity,
        "deadline_ms": deadline_ms,
        "per_worker_load_factor": per_worker_factor,
        "router_seed": router_seed,
        "traffic_seed": traffic_seed,
        "cpu_count": cores,
        "same_load_contract_applicable": cores >= 2,
        "points": points,
        "scaling_efficiency": scaling,
        "same_load_offered_rate_hz": same_rate * n_streams,
        "same_load_1w": rep1,
        "same_load_2w": rep2,
        "same_load_goodput_ratio_2v1": ratio,
    }


def _movable_skew_engine(plan, graphs, engines):
    """Pick the perturbation target: the engine with the most *movable*
    planned work (current analytic occupancy minus the minimum any plan
    must leave there given the counter-phased pair structure). Skewing an
    engine whose share is already minimal tests nothing — the planner has
    nowhere to move it."""
    from repro.core.cost_model import ANALYTIC

    E = len(engines)
    current = [0.0] * E
    minimum = [0.0] * E
    for mi, segs in enumerate(plan.ir.segments):
        g = graphs[mi]
        e1, e2 = mi % E, (mi + 1) % E
        for seg in segs:
            current[seg.engine] += sum(
                ANALYTIC.layer_time(g[i], engines[seg.engine]) for i in range(seg.lo, seg.hi)
            )
        minimum[e1] += ANALYTIC.layer_time(g[0], engines[e1])
        minimum[e2] += ANALYTIC.layer_time(g[len(g) - 1], engines[e2])
    movable = [c - m for c, m in zip(current, minimum)]
    return max(range(E), key=lambda e: movable[e])


def run_replan_scenario(
    img: int,
    base: int,
    norm: str,
    skew: float = 3.0,
    n_pix: int = 2,
    frames_per_window: int = 8,
    warm_windows: int = 3,
    pre_windows: int = 3,
    post_windows: int = 6,
) -> dict:
    """Perturbation-recovery: calibrate, skew one engine, watch the
    replanner restore throughput with zero dropped frames."""
    import jax
    import numpy as np

    from repro.core.constraints import DLA_ANALOGUE_CONSTRAINTS
    from repro.core.cost_model import ANALYTIC
    from repro.core.engine import jetson_orin_engines
    from repro.serve import ReplanConfig, StreamExecutor, build_pix_yolo_serving, build_replanner

    models, plan, streams, _ = build_pix_yolo_serving(
        img=img, base=base, n_pix=n_pix, n_yolo=1, norm=norm
    )
    graphs = [m.graph for m in models]
    gpu, dla = jetson_orin_engines(constraints_dla=DLA_ANALOGUE_CONSTRAINTS)
    engines = [dla, gpu]  # plan order (see build_pix_yolo_serving)
    skew_idx = _movable_skew_engine(plan, graphs, engines)
    skew_name = engines[skew_idx].name

    pert = {"on": False, "calib": 0.0}
    span_cache: dict[tuple, float] = {}

    def analytic_span(seg):
        key = (seg.model_index, seg.engine, seg.lo, seg.hi)
        if key not in span_cache:
            g = graphs[seg.model_index]
            e = engines[seg.engine]
            span_cache[key] = sum(ANALYTIC.layer_time(g[i], e) for i in range(seg.lo, seg.hi))
        return span_cache[key]

    def delay_fn(seg):
        # a skew x slowdown of one engine: every segment placed there
        # stalls for (skew-1) x its calibrated wall time, however the
        # active plan slices the spans
        if not pert["on"] or seg.engine != skew_idx:
            return 0.0
        return (skew - 1.0) * pert["calib"] * analytic_span(seg)

    # the scenario owns calibration: warmup_obs is effectively disabled so
    # the baseline comes only from the explicit calibrate() below (never
    # from still-settling compile-era scales), and the EMA is given enough
    # hysteresis ticks to converge before the planner reads it
    replanner = build_replanner(
        models,
        config=ReplanConfig(warmup_obs=10**9, ema_alpha=0.35, hysteresis=4),
    )
    ex = StreamExecutor(models, plan, streams, max_queue=8, segment_delay_fn=delay_fn)

    frames: dict[str, list] = {s.name: [] for s in streams}
    submitted = 0

    def run_window(wi: int) -> float:
        nonlocal submitted
        t0 = time.perf_counter()
        c0 = len(ex.completions)
        for t in range(frames_per_window):
            for i, s in enumerate(streams):
                f = jax.random.normal(jax.random.key(100_000 * wi + 997 * i + t), (1, img, img, 3))
                assert ex.submit(i, f), "queue refused a frame (zero-drop violated)"
                frames[s.name].append(f)
                submitted += 1
            ex.tick()
        ex.run_until_drained()
        return (len(ex.completions) - c0) / (time.perf_counter() - t0)

    # 1. warm the executor alone (jit compiles), then attach + calibrate
    for wi in range(warm_windows):
        run_window(wi)
    replanner.attach(ex)
    run_window(warm_windows)  # feed the EMA with steady-state observations
    run_window(warm_windows + 1)
    replanner.calibrate()

    # 2. pre-perturbation reference
    pre = [run_window(100 + wi) for wi in range(pre_windows)]
    pre_fps = sorted(pre)[len(pre) // 2]

    # 3. perturb + recovery curve
    pert["calib"] = replanner.online.scale(skew_name)
    pert["on"] = True
    windows = []
    for wi in range(post_windows):
        fps = run_window(200 + wi)
        windows.append(
            {
                "window": wi,
                "fps": fps,
                "vs_pre": fps / pre_fps,
                "swaps": sum(e.swapped for e in replanner.events),
                "plan_revision": ex.plan_revision,
                "partitions": list(ex.plan.partitions),
            }
        )
    # recovered = windows strictly after the swap count stabilized (the
    # window containing the last swap still pays detection + warmup)
    final_swaps = windows[-1]["swaps"] if windows else 0
    settle = next((i for i, w in enumerate(windows) if w["swaps"] == final_swaps), 0)
    post_swap = [w["fps"] for w in windows[settle + 1 :]] or [windows[-1]["fps"]]
    recovered_fps = sorted(post_swap)[len(post_swap) // 2]

    # 4. zero-drop + output equality vs the final plan run from the start
    zero_drop = len(ex.completions) == submitted
    ref = StreamExecutor(models, ex.plan, streams, max_queue=8)
    outputs_match = True
    n_frames = len(frames[streams[0].name])
    for t in range(n_frames):
        for i, s in enumerate(streams):
            assert ref.submit(i, frames[s.name][t])
        ref.tick()
        if (t + 1) % frames_per_window == 0:
            ref.run_until_drained()  # mirror the scenario's window boundaries
    ref_outs = ref.run_until_drained()
    for s in streams:
        for a, b in zip(ex.outputs[s.name], ref_outs[s.name]):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                if not np.allclose(np.asarray(la), np.asarray(lb), atol=2e-3, rtol=1e-2):
                    outputs_match = False

    rep = replanner.summary()
    return {
        "skew": skew,
        "skew_engine": skew_name,
        "initial_partitions": list(plan.partitions),
        "final_partitions": list(ex.plan.partitions),
        "plan_revision": ex.plan_revision,
        "pre_fps": pre_fps,
        "perturbed_fps": min(w["fps"] for w in windows) if windows else float("nan"),
        "recovered_fps": recovered_fps,
        "recovery_ratio": recovered_fps / pre_fps,
        "zero_drop": zero_drop,
        "outputs_match_final_plan": outputs_match,
        "windows": windows,
        "swaps": rep["swaps"],
        "replans": rep["replans"],
        "scales": rep["scales"],
        "events": rep["events"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny fast sweep for CI")
    ap.add_argument("--streams", default=None, help="comma-separated pix-stream counts")
    ap.add_argument("--frames", type=int, default=None, help="frames per stream")
    ap.add_argument("--img", type=int, default=None)
    ap.add_argument("--base", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--cost", choices=("analytic", "measured", "blended"), default="analytic")
    ap.add_argument("--cost-cache", default=None, help="JSON cache for measured layer timings")
    ap.add_argument("--norm", choices=("batch", "instance", "group"), default="batch")
    ap.add_argument("--search", choices=("auto", "exhaustive", "beam", "descent"), default="auto")
    ap.add_argument(
        "--skip-dispatch-compare",
        action="store_true",
        help="skip the overlapped-vs-serialized executor comparison point",
    )
    ap.add_argument(
        "--skip-replan-scenario",
        action="store_true",
        help="skip the online re-planning perturbation-recovery scenario",
    )
    ap.add_argument(
        "--skip-granularity-compare",
        action="store_true",
        help="skip the coarse-vs-fine planning granularity comparison",
    )
    ap.add_argument(
        "--skip-multicut-compare",
        action="store_true",
        help="skip the max_cuts (k-segment route) sweep",
    )
    ap.add_argument(
        "--skip-impl-compare",
        action="store_true",
        help="skip the implementation-planning (xla/auto/pallas) sweep",
    )
    ap.add_argument(
        "--impl",
        choices=("auto", "xla", "pallas"),
        default="xla",
        help="implementation-planning mode for the main stream sweep's plan",
    )
    ap.add_argument(
        "--skip-openloop-sweep",
        action="store_true",
        help="skip the open-loop traffic / SLO / admission-control sweep",
    )
    ap.add_argument(
        "--skip-batching-sweep",
        action="store_true",
        help="skip the continuous-batching (max_batch) sweep",
    )
    ap.add_argument(
        "--batching-max-batches",
        default="1,4,8",
        help="comma-separated coalescer caps for the batching sweep",
    )
    ap.add_argument(
        "--batch-hold-ms",
        type=float,
        default=2.0,
        help="slack-gated hold window for the batching sweep (ms)",
    )
    ap.add_argument(
        "--skip-fleet-sweep",
        action="store_true",
        help="skip the replicated-fleet scaling sweep",
    )
    ap.add_argument(
        "--fleet-replicas",
        default="1,2,4",
        help="comma-separated replica counts for the fleet sweep",
    )
    ap.add_argument(
        "--skip-proc-fleet-sweep",
        action="store_true",
        help="skip the multi-process (worker) fleet scaling sweep",
    )
    ap.add_argument(
        "--proc-fleet-sweep",
        action="store_true",
        help="run the proc-fleet sweep on a TPU backend too, where it is off by "
        "default: one process holds a chip, so build_server refuses workers there",
    )
    ap.add_argument(
        "--proc-fleet-workers",
        default="1,2,4",
        help="comma-separated worker-process counts for the proc-fleet sweep",
    )
    ap.add_argument("--router-seed", type=int, default=0, help="fleet router tie-break seed")
    ap.add_argument("--traffic-seed", type=int, default=0, help="fleet sweep arrival seed")
    ap.add_argument(
        "--openloop-horizon",
        type=float,
        default=1.5,
        help="open-loop arrival horizon per load point (seconds)",
    )
    ap.add_argument(
        "--max-cuts-sweep",
        default="1,2,3",
        help="comma-separated cut budgets for the multi-cut comparison",
    )
    ap.add_argument(
        "--granularity-stride",
        type=int,
        default=1,
        help="fine-granularity candidate stride for the comparison point",
    )
    ap.add_argument("--skew", type=float, default=3.0, help="perturbation cost skew factor")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    from repro.core.cost_model import make_cost_provider
    from repro.launch import compile_cache

    compile_cache.enable()

    provider = make_cost_provider(args.cost, cache_path=args.cost_cache)

    if args.smoke:
        counts = [1, 2, 4]
        frames = args.frames or 3
        img = args.img or 32
    else:
        counts = [1, 2, 4, 8]
        frames = args.frames or 12
        img = args.img or 64
    if args.streams:
        counts = [int(x) for x in args.streams.split(",")]

    models, plan = build_models(img, args.base, args.norm, provider, args.search, args.impl)
    # warm both executor configurations (jitted segment executables AND the
    # eager per-op caches) at the widest stream count so the sweep measures
    # steady state, not first-call tracing
    warm_k = max(counts)
    run_point(models, plan, warm_k, 1, img, args.microbatch, args.norm, "overlapped", True)
    run_point(models, plan, warm_k, 1, img, args.microbatch, args.norm, "serialized", False)

    results = []
    for k in counts:
        r = run_point(models, plan, k, frames, img, args.microbatch, args.norm)
        results.append(r)
        print(
            f"streams={r['streams']:>2}  aggregate={r['aggregate_fps']:7.2f} FPS  "
            f"p50={r['latency_p50_ms']:8.1f} ms  p99={r['latency_p99_ms']:8.1f} ms  "
            f"overlap={r['overlap_efficiency']:.3f}"
        )

    peak = max(results, key=lambda r: r["aggregate_fps"])

    dispatch_compare = None
    if not args.skip_dispatch_compare:
        # three executor configurations at the peak stream count:
        #   serialized+eager — the legacy per-op path with per-segment sync
        #   serialized+jit   — fused segments, still synced per engine call
        #   overlapped+jit   — the new default (async dispatch, resolve-only
        #                      sync); vs serialized+jit isolates the overlap
        #                      win, vs serialized+eager is the full refactor
        k = peak["pix_streams"]
        cmp_frames = max(frames, 8)  # tiny frame counts are too noisy to rank
        configs = [
            ("serialized_eager", "serialized", False),
            ("serialized_jit", "serialized", True),
            ("overlapped_jit", "overlapped", True),
        ]
        samples: dict[str, list[dict]] = {name: [] for name, _, _ in configs}
        for _ in range(3):  # interleaved repeats cancel container drift
            for name, dispatch, jit in configs:
                samples[name].append(
                    run_point(
                        models, plan, k, cmp_frames, img, args.microbatch, args.norm,
                        dispatch=dispatch, jit_segments=jit,
                    )
                )
        med = {
            name: sorted(rs, key=lambda r: r["aggregate_fps"])[len(rs) // 2]
            for name, rs in samples.items()
        }
        dispatch_compare = {
            "pix_streams": k,
            "frames_per_stream": cmp_frames,
            "repeats": 3,
            "serialized_eager_fps": med["serialized_eager"]["aggregate_fps"],
            "serialized_jit_fps": med["serialized_jit"]["aggregate_fps"],
            "overlapped_jit_fps": med["overlapped_jit"]["aggregate_fps"],
            "overlap_speedup": med["overlapped_jit"]["aggregate_fps"]
            / med["serialized_jit"]["aggregate_fps"],
            "total_speedup": med["overlapped_jit"]["aggregate_fps"]
            / med["serialized_eager"]["aggregate_fps"],
            "serialized_overlap_efficiency": med["serialized_jit"]["overlap_efficiency"],
            "overlapped_overlap_efficiency": med["overlapped_jit"]["overlap_efficiency"],
        }
        print(
            f"dispatch compare @ {k} pix streams (median of 3): "
            f"serialized/eager={dispatch_compare['serialized_eager_fps']:.2f} "
            f"serialized/jit={dispatch_compare['serialized_jit_fps']:.2f} "
            f"overlapped/jit={dispatch_compare['overlapped_jit_fps']:.2f} FPS "
            f"(overlap x{dispatch_compare['overlap_speedup']:.2f}, "
            f"total x{dispatch_compare['total_speedup']:.2f})"
        )

    granularity_compare = None
    if not args.skip_granularity_compare:
        granularity_compare = run_granularity_compare(
            img, args.base, args.norm, max(frames, 8), args.microbatch, args.granularity_stride
        )
        print(
            f"granularity compare: coarse plan {granularity_compare['coarse_plan_cycle_ms_rescored_fine']:.3f} ms "
            f"vs fine plan {granularity_compare['fine_plan_cycle_ms']:.3f} ms "
            f"(x{1.0 / granularity_compare['plan_cost_ratio']:.2f} analytic)  "
            f"FPS {granularity_compare['coarse_fps']:.2f} -> {granularity_compare['fine_fps']:.2f} "
            f"(x{granularity_compare['fps_ratio']:.2f} measured)"
        )

    multicut_compare = None
    if not args.skip_multicut_compare:
        cuts_list = tuple(int(x) for x in args.max_cuts_sweep.split(","))
        multicut_compare = run_multicut_compare(
            img, args.base, args.norm, max(frames, 8), args.microbatch, cuts_list
        )
        pts = multicut_compare["points"]
        print(
            "multicut compare: "
            + "  ".join(
                f"max_cuts={mc}: {pts[str(mc)]['plan_cycle_ms']:.3f} ms plan / "
                f"{pts[str(mc)]['aggregate_fps']:.2f} FPS"
                for mc in cuts_list
            )
            + f"  (best={multicut_compare['best_max_cuts']}, "
            f"analytic x{multicut_compare['plan_cost_ratio']:.2f}, "
            f"FPS x{multicut_compare['fps_ratio']:.2f})"
        )

    impl_compare = None
    if not args.skip_impl_compare:
        impl_compare = run_impl_compare(
            img, args.base, args.norm, max(frames, 4), args.microbatch
        )
        pts = impl_compare["points"]
        print(
            "impl compare (measured costs): "
            + "  ".join(
                f"{im}: {pts[im]['plan_cycle_ms']:.3f} ms plan "
                f"({pts[im]['pallas_segments']} fused seg) / "
                f"{pts[im]['aggregate_fps']:.2f} FPS"
                for im in impl_compare["impls"]
            )
            + f"  (auto/xla plan ratio {impl_compare['auto_vs_xla_plan_ratio']:.3f}, "
            f"never_worse={impl_compare['auto_never_worse']})"
        )

    openloop = None
    if not args.skip_openloop_sweep:
        openloop = run_openloop_sweep(
            img, args.base, args.norm, args.microbatch, horizon_s=args.openloop_horizon
        )
        pts = openloop["points"]
        print(
            f"openloop sweep (capacity={openloop['capacity_fps']:.2f} FPS, "
            f"deadline={openloop['deadline_ms']:.0f} ms): "
            + "  ".join(
                f"{lf}x: goodput={pts[str(lf)]['goodput_fps']:.2f} "
                f"p99={pts[str(lf)]['latency_p99_ms']:.0f}ms "
                f"drop={pts[str(lf)]['dropped']}"
                for lf in openloop["load_factors"]
            )
            + f"  queue-only@{max(openloop['load_factors'])}x: "
            f"goodput={openloop['queue_only_top']['goodput_fps']:.2f} "
            f"p99={openloop['queue_only_top']['latency_p99_ms']:.0f}ms "
            f"(shed/queue goodput x{openloop['shed_vs_queue_goodput_ratio']:.2f})"
        )

    batching = None
    if not args.skip_batching_sweep:
        batching = run_batching_sweep(
            img, args.base, args.microbatch,
            max_batches=tuple(int(x) for x in args.batching_max_batches.split(",")),
            horizon_s=min(args.openloop_horizon, 1.0),
            hold_ms=args.batch_hold_ms,
        )
        pts = batching["points"]
        top = str(max(batching["load_factors"]))
        print(
            f"batching sweep (capacity={batching['capacity_fps']:.2f} FPS, "
            f"deadline={batching['deadline_ms']:.0f} ms, hold={batching['hold_ms']}ms): "
            + "  ".join(
                f"B={mb}@{top}x: goodput={pts[str(mb)][top]['goodput_fps']:.2f} "
                f"eff_batch={pts[str(mb)][top]['mean_effective_batch']:.2f} "
                f"p99={pts[str(mb)][top]['latency_p99_ms']:.0f}ms"
                for mb in batching["max_batches"]
            )
            + f"  batched/unbatched goodput x{batching['batched_vs_unbatched_goodput_ratio_3x']:.2f}"
            f"  held_then_missed={batching['held_then_missed_total']}"
        )

    fleet = None
    if not args.skip_fleet_sweep:
        fleet = run_fleet_sweep(
            img, args.base, args.norm, args.microbatch,
            replica_counts=tuple(int(x) for x in args.fleet_replicas.split(",")),
            horizon_s=min(args.openloop_horizon, 1.0),
            router_seed=args.router_seed,
            traffic_seed=args.traffic_seed,
        )
        pts = fleet["points"]
        print(
            f"fleet sweep (capacity={fleet['capacity_fps']:.2f} FPS, "
            f"deadline={fleet['deadline_ms']:.0f} ms): "
            + "  ".join(
                f"R={R}: goodput={pts[str(R)]['goodput_fps']:.2f} "
                f"eff={fleet['scaling_efficiency'][str(R)]:.2f} "
                f"imb={pts[str(R)]['router_imbalance']:.2f}"
                for R in fleet["replica_counts"]
            )
            + f"  same-load 2R/1R goodput x{fleet['same_load_goodput_ratio_2v1']:.2f}"
        )

    proc_fleet = None
    import jax

    if not args.skip_proc_fleet_sweep and (
        args.proc_fleet_sweep or jax.default_backend() != "tpu"
    ):
        proc_fleet = run_proc_fleet_sweep(
            img, args.base, args.norm, args.microbatch,
            worker_counts=tuple(int(x) for x in args.proc_fleet_workers.split(",")),
            horizon_s=min(args.openloop_horizon, 1.0),
            router_seed=args.router_seed,
            traffic_seed=args.traffic_seed,
        )
        pts = proc_fleet["points"]
        print(
            f"proc-fleet sweep (capacity={proc_fleet['capacity_fps']:.2f} FPS, "
            f"deadline={proc_fleet['deadline_ms']:.0f} ms): "
            + "  ".join(
                f"W={W}: goodput={pts[str(W)]['goodput_fps']:.2f} "
                f"eff={proc_fleet['scaling_efficiency'][str(W)]:.2f} "
                f"imb={pts[str(W)]['router_imbalance']:.2f} "
                f"spawn={pts[str(W)]['startup_s']:.1f}s"
                for W in proc_fleet["worker_counts"]
            )
            + f"  same-load 2W/1W goodput x{proc_fleet['same_load_goodput_ratio_2v1']:.2f}"
            + (
                ""
                if proc_fleet["same_load_contract_applicable"]
                else f" (single-core host, {proc_fleet['cpu_count']} core: not gated)"
            )
        )

    replan_scenario = None
    if not args.skip_replan_scenario:
        replan_scenario = run_replan_scenario(img, args.base, args.norm, skew=args.skew)
        print(
            f"replan scenario: skew x{args.skew} on {replan_scenario['skew_engine']}  "
            f"pre={replan_scenario['pre_fps']:.2f} FPS  "
            f"dip={replan_scenario['perturbed_fps']:.2f}  "
            f"recovered={replan_scenario['recovered_fps']:.2f} "
            f"({replan_scenario['recovery_ratio']:.1%} of pre)  "
            f"swaps={replan_scenario['swaps']}  "
            f"zero_drop={replan_scenario['zero_drop']}  "
            f"outputs_match={replan_scenario['outputs_match_final_plan']}"
        )

    if args.cost_cache and hasattr(provider, "save"):
        provider.save()  # measured AND blended both persist their timings

    payload = {
        "bench": "multi_stream_serve",
        "smoke": bool(args.smoke),
        "img_size": img,
        "frames_per_stream": frames,
        "microbatch": args.microbatch,
        "norm": args.norm,
        "cost_provider": args.cost,
        "impl": args.impl,
        "planner_search": results[0]["planner_search"] if results else args.search,
        "platform": platform.platform(),
        "hostname": socket.gethostname(),
        "aggregate_fps": peak["aggregate_fps"],
        "latency_p50_ms": peak["latency_p50_ms"],
        "latency_p99_ms": peak["latency_p99_ms"],
        "overlap_efficiency": peak["overlap_efficiency"],
        "dispatch_compare": dispatch_compare,
        "granularity_compare": granularity_compare,
        "multicut_compare": multicut_compare,
        "impl_compare": impl_compare,
        "openloop": openloop,
        "batching": batching,
        "fleet": fleet,
        "proc_fleet": proc_fleet,
        "replan_scenario": replan_scenario,
        "results": results,
    }

    # runner identity for the per-machine trend store: BENCH_MACHINE lets
    # CI pin a stable key (ephemeral runners get a fresh hostname per job,
    # which would never match its own history)
    payload["machine"] = os.environ.get(
        "BENCH_MACHINE", f"{payload['hostname']}|{jax.default_backend()}"
    )
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
