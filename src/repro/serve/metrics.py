"""Per-stream serving metrics: latency percentiles, throughput, and
per-tick dispatch-overlap efficiency.

Latencies are wall-clock submit→completion seconds as stamped by the
executor. Percentiles use the nearest-rank method on the recorded sample
(exact for the small counts a bench run produces; no interpolation
surprises when comparing runs).

Overlap efficiency measures how much of each executor tick the host spent
usefully dispatching (or doing bookkeeping) versus blocked waiting on
device results: ``1 - blocked_s / wall_s``. The serialized dispatch mode
synchronizes after every engine segment, so most of its tick is blocked
time; the overlapped mode only synchronizes when a frame completes, so
counter-phased engine segments genuinely run concurrently and the
efficiency approaches 1.
"""
from __future__ import annotations

import dataclasses
import math
from collections import deque


@dataclasses.dataclass
class TickStats:
    """Host-side timing of one executor tick.

    ``wall_s`` and ``blocked_s`` are timed on every tick. ``engine_wait``
    is the per-engine host-time breakdown of the tick, ``{engine_name:
    (issue_s, transfer_s, resolve_s)}`` — dispatch time spent issuing that
    engine's segments, time placing states onto it, and time blocked
    waiting for its results. It is a view over the tick's
    ``executor.dispatch`` (self time), ``executor.place`` and
    ``executor.block`` spans, present only while the executor's span
    recorder is on (``serve.tracing``); None otherwise. Resolve-wait
    dominating the tick is the no-overlap signature the coalescer
    attacks."""

    tick: int
    wall_s: float
    blocked_s: float  # time inside block_until_ready during this tick
    segments: int  # engine segment calls issued this tick
    engine_wait: dict | None = None  # engine -> (issue_s, transfer_s, resolve_s), while tracing

    @property
    def overlap_efficiency(self) -> float:
        if self.wall_s <= 0:
            return 1.0
        return max(0.0, 1.0 - self.blocked_s / self.wall_s)


@dataclasses.dataclass(frozen=True)
class SwapStall:
    """Hot-path cost of one plan hot-swap.

    ``prepare_s`` is the segment-executable warmup (compile + first
    execution on zero states); ``background=True`` means it ran in the
    replanner's worker thread, so only ``swap_s`` stalled the tick
    thread. This is the number that decides whether ``prepare_plan``
    belongs in the worker on a given backend (compile times dominate on
    real accelerators)."""

    tick: int
    prepare_s: float
    swap_s: float
    background: bool
    # True when only the drifted model's route changed (the re-planner
    # held every other model fixed) — cheaper to prepare and lower-risk
    # than a full-plan swap
    partial: bool = False

    @property
    def hot_path_s(self) -> float:
        """Time the executor's tick thread was stalled by this swap."""
        return self.swap_s + (0.0 if self.background else self.prepare_s)


def swap_stall_summary(stalls: list[SwapStall]) -> dict:
    """Aggregate swap-stall accounting for one serving run."""
    if not stalls:
        return {"swaps": 0, "hot_path_stall_ms": 0.0, "hot_path_stall_max_ms": 0.0,
                "prepare_ms": 0.0, "background_prepares": 0,
                "partial_swaps": 0, "full_swaps": 0}
    return {
        "swaps": len(stalls),
        "hot_path_stall_ms": sum(s.hot_path_s for s in stalls) * 1e3,
        "hot_path_stall_max_ms": max(s.hot_path_s for s in stalls) * 1e3,
        "prepare_ms": sum(s.prepare_s for s in stalls) * 1e3,
        "background_prepares": sum(s.background for s in stalls),
        "partial_swaps": sum(s.partial for s in stalls),
        "full_swaps": sum(not s.partial for s in stalls),
    }


def overlap_summary(ticks: list[TickStats]) -> dict:
    """Aggregate per-tick overlap efficiency for one serving run."""
    if not ticks:
        return {"ticks": 0, "overlap_efficiency": math.nan, "blocked_s": 0.0, "tick_wall_s": 0.0}
    wall = sum(t.wall_s for t in ticks)
    blocked = sum(t.blocked_s for t in ticks)
    return {
        "ticks": len(ticks),
        "overlap_efficiency": max(0.0, 1.0 - blocked / wall) if wall > 0 else math.nan,
        "blocked_s": blocked,
        "tick_wall_s": wall,
    }


def engine_wait_summary(ticks: list[TickStats]) -> dict:
    """Per-engine idle-time breakdown over a run: where each engine's
    host time went — issue (dispatch), transfer (placement), resolve
    (blocked on results) — as absolute seconds and as fractions of the
    total tick wall. The diagnostic behind a flat overlap_speedup: when
    ``resolve_frac`` dominates, segments are serializing on the host
    instead of overlapping, which is exactly what batched executables
    amortize."""
    wall = sum(t.wall_s for t in ticks)
    acc: dict[str, list[float]] = {}
    for t in ticks:
        if not t.engine_wait:
            continue
        for name, w in t.engine_wait.items():
            a = acc.setdefault(name, [0.0, 0.0, 0.0])
            a[0] += w[0]
            a[1] += w[1]
            a[2] += w[2]
    return {
        name: {
            "issue_s": a[0],
            "transfer_s": a[1],
            "resolve_s": a[2],
            "issue_frac": a[0] / wall if wall > 0 else math.nan,
            "transfer_frac": a[1] / wall if wall > 0 else math.nan,
            "resolve_frac": a[2] / wall if wall > 0 else math.nan,
        }
        for name, a in sorted(acc.items())
    }


def segment_summary(observations) -> dict:
    """Aggregate profiled per-segment wall times by (model, engine, span).

    The executor's profiled ticks produce ``SegmentObservation``s; this is
    the report-side rollup — mean/p50 wall per distinct segment binding,
    so a serving report shows where each plan revision actually spent its
    time (the same numbers the replanner's EMA consumes).
    """
    by_seg: dict[tuple, list[float]] = {}
    for o in observations:
        by_seg.setdefault((o.model_index, o.engine, o.lo, o.hi), []).append(o.wall_s)
    return {
        f"m{mi}@E{eng}[{lo}:{hi})": {
            "samples": len(ws),
            "wall_mean_ms": sum(ws) / len(ws) * 1e3,
            "wall_p50_ms": percentile(ws, 50) * 1e3,
        }
        for (mi, eng, lo, hi), ws in sorted(by_seg.items())
    }


def percentile(samples: list[float], pct: float) -> float:
    """Nearest-rank percentile; pct in [0, 100]."""
    if not samples:
        return math.nan
    s = sorted(samples)
    rank = max(1, math.ceil(pct / 100.0 * len(s)))
    return s[min(rank, len(s)) - 1]


@dataclasses.dataclass
class StreamMetrics:
    name: str
    latencies_s: list[float] = dataclasses.field(default_factory=list)
    completed: int = 0
    in_slo: int = 0  # completions within the stream's deadline

    def record(self, latency_s: float, met_slo: bool = True):
        self.latencies_s.append(latency_s)
        self.completed += 1
        if met_slo:
            self.in_slo += 1

    def summary(self) -> dict:
        return {
            "completed": self.completed,
            "latency_p50_ms": percentile(self.latencies_s, 50) * 1e3,
            "latency_p99_ms": percentile(self.latencies_s, 99) * 1e3,
            "latency_mean_ms": (
                sum(self.latencies_s) / len(self.latencies_s) * 1e3 if self.latencies_s else math.nan
            ),
        }


@dataclasses.dataclass
class TierMetrics:
    """Per-priority-tier admission and goodput accounting.

    ``offered`` counts every open-loop arrival for the tier's streams;
    the admission ledger splits it into ``admitted`` (untouched),
    ``shed_res``/``shed_route`` (admitted degraded) and ``dropped``
    (evicted or rejected). ``in_slo`` counts completions within their
    stream's deadline — goodput-under-SLO is ``in_slo / wall``."""

    tier: int
    offered: int = 0
    admitted: int = 0
    shed_res: int = 0
    shed_route: int = 0
    dropped: int = 0
    completed: int = 0
    in_slo: int = 0
    latencies_s: list[float] = dataclasses.field(default_factory=list)

    def summary(self, wall_s: float) -> dict:
        return {
            "offered": self.offered,
            "admitted": self.admitted,
            "shed_res": self.shed_res,
            "shed_route": self.shed_route,
            "dropped": self.dropped,
            "completed": self.completed,
            "completed_in_slo": self.in_slo,
            "goodput_fps": self.in_slo / wall_s if wall_s > 0 else math.inf,
            "slo_attainment": self.in_slo / self.completed if self.completed else math.nan,
            "latency_p99_ms": percentile(self.latencies_s, 99) * 1e3,
        }


class ServeMetrics:
    """Aggregates completions across streams for one serving run.

    ``slos`` (stream name -> ``SLOPolicy`` or None) turns on SLO
    accounting: completions are checked against their stream's deadline,
    bucketed per priority tier, and a sliding window of recent SLO
    outcomes feeds the re-planner's load-pressure signal
    (``recent_slo_miss_rate``). Streams without a policy count as tier 0
    with an infinite deadline, so closed-loop reports are unchanged."""

    def __init__(self, stream_names: list[str], slos: dict | None = None, recent_window: int = 64):
        self.streams = {n: StreamMetrics(n) for n in stream_names}
        self.ticks: list[TickStats] = []
        self.slos = dict(slos) if slos else {}
        self.tiers: dict[int, TierMetrics] = {}
        self._recent: deque[bool] = deque(maxlen=recent_window)  # True = deadline met
        # continuous-batching occupancy ledger: effective-batch histogram
        # over completions (each frame counts the real frames in its
        # flight), plus the held-then-missed contract counter — a frame
        # the coalescer held that then missed its deadline. The hold rule
        # is built to keep that at exactly 0.
        self.batch_occupancy: dict[int, int] = {}
        self.held_frames = 0
        self.held_then_missed = 0
        # queue wait: submit to admission into a flight, summed over the
        # completions that carry it
        self.queue_wait_s = 0.0
        self.queue_frames = 0

    def _tier(self, stream: str) -> TierMetrics:
        slo = self.slos.get(stream)
        t = slo.tier if slo is not None else 0
        tm = self.tiers.get(t)
        if tm is None:
            tm = self.tiers[t] = TierMetrics(t)
        return tm

    def record(self, stream: str, latency_s: float, degrade: int = 0,
               batch: int = 1, held: bool = False, queue_wait_s: float | None = None):
        slo = self.slos.get(stream)
        met = slo is None or latency_s <= slo.deadline_s
        self.streams[stream].record(latency_s, met_slo=met)
        tm = self._tier(stream)
        tm.completed += 1
        tm.latencies_s.append(latency_s)
        if met:
            tm.in_slo += 1
        self._recent.append(met)
        b = max(int(batch), 1)
        self.batch_occupancy[b] = self.batch_occupancy.get(b, 0) + 1
        if held:
            self.held_frames += 1
            if not met:
                self.held_then_missed += 1
        if queue_wait_s is not None:
            self.queue_wait_s += queue_wait_s
            self.queue_frames += 1

    def mean_effective_batch(self) -> float:
        """Frame-weighted mean of the batch each completion rode in."""
        total = sum(self.batch_occupancy.values())
        if not total:
            return math.nan
        return sum(b * n for b, n in self.batch_occupancy.items()) / total

    def record_arrival(self, stream: str):
        self._tier(stream).offered += 1

    def record_admission(self, stream: str, decision: str):
        """Fold one admission decision (``serve.admission`` constants)."""
        tm = self._tier(stream)
        if decision == "admit":
            tm.admitted += 1
        elif decision == "shed_res":
            tm.shed_res += 1
        elif decision == "shed_route":
            tm.shed_route += 1
        elif decision == "drop":
            tm.dropped += 1
        else:
            raise ValueError(f"unknown admission decision {decision!r}")

    def record_tick(self, stats: TickStats):
        self.ticks.append(stats)

    def recent_slo_miss_rate(self) -> float:
        """Fraction of the last ``recent_window`` completions that missed
        their deadline — the re-planner's SLO-pressure signal. 0.0 until
        anything completes."""
        if not self._recent:
            return 0.0
        return 1.0 - sum(self._recent) / len(self._recent)

    def report(self, wall_s: float) -> dict:
        all_lat = [l for m in self.streams.values() for l in m.latencies_s]
        total = sum(m.completed for m in self.streams.values())
        in_slo = sum(m.in_slo for m in self.streams.values())
        rep = {
            "streams": len(self.streams),
            "frames": total,
            "wall_s": wall_s,
            "aggregate_fps": total / wall_s if wall_s > 0 else math.inf,
            "latency_p50_ms": percentile(all_lat, 50) * 1e3,
            "latency_p99_ms": percentile(all_lat, 99) * 1e3,
            "overlap": overlap_summary(self.ticks),
            "engines": engine_wait_summary(self.ticks),
            "batching": {
                "occupancy": {str(b): n for b, n in sorted(self.batch_occupancy.items())},
                "mean_effective_batch": self.mean_effective_batch(),
                "held_frames": self.held_frames,
                "held_then_missed": self.held_then_missed,
            },
            # mean submit-to-admission wait; None before any completion carries one
            "queue": {"frames": self.queue_frames, "wait_ms_mean": (
                1e3 * self.queue_wait_s / self.queue_frames if self.queue_frames else None)},
            "per_stream": {n: m.summary() for n, m in self.streams.items()},
        }
        if self.slos:
            rep["goodput_fps"] = in_slo / wall_s if wall_s > 0 else math.inf
            rep["slo_miss_rate_recent"] = self.recent_slo_miss_rate()
            rep["tiers"] = {t: tm.summary(wall_s) for t, tm in sorted(self.tiers.items())}
            rep["admission"] = {
                "offered": sum(tm.offered for tm in self.tiers.values()),
                "admitted": sum(tm.admitted for tm in self.tiers.values()),
                "shed_res": sum(tm.shed_res for tm in self.tiers.values()),
                "shed_route": sum(tm.shed_route for tm in self.tiers.values()),
                "dropped": sum(tm.dropped for tm in self.tiers.values()),
            }
        return rep

    # -- cross-process serialization (see serve.multiproc) -------------------

    def to_payload(self) -> dict:
        """The full ledger as a JSON-able dict: fleet workers ship this
        over the RPC pipe and the front rebuilds a live ``ServeMetrics``
        with ``metrics_from_payload`` so the existing ``merge_metrics`` /
        ``fleet_report`` machinery works across process boundaries."""
        return {
            "streams": {
                n: {"latencies_s": list(m.latencies_s), "completed": m.completed,
                    "in_slo": m.in_slo}
                for n, m in self.streams.items()
            },
            "slos": {
                n: {"deadline_ms": p.deadline_ms, "tier": p.tier, "name": p.name}
                for n, p in self.slos.items() if p is not None
            },
            "tiers": {
                str(t): {
                    "offered": tm.offered, "admitted": tm.admitted,
                    "shed_res": tm.shed_res, "shed_route": tm.shed_route,
                    "dropped": tm.dropped, "completed": tm.completed,
                    "in_slo": tm.in_slo, "latencies_s": list(tm.latencies_s),
                }
                for t, tm in self.tiers.items()
            },
            "ticks": [
                [t.tick, t.wall_s, t.blocked_s, t.segments, t.engine_wait]
                for t in self.ticks
            ],
            "recent": [bool(b) for b in self._recent],
            "recent_window": self._recent.maxlen,
            "batch_occupancy": {str(b): n for b, n in self.batch_occupancy.items()},
            "held_frames": self.held_frames,
            "held_then_missed": self.held_then_missed,
            "queue_wait_s": self.queue_wait_s,
            "queue_frames": self.queue_frames,
        }


def metrics_from_payload(payload: dict) -> ServeMetrics:
    """Rebuild a live ``ServeMetrics`` from ``ServeMetrics.to_payload``.
    The reconstruction is exact — stream/tier counters, latency samples,
    tick log, and the recent-SLO window all round-trip — so a merged
    fleet report over worker payloads matches the in-process merge."""
    from .traffic import SLOPolicy  # local: traffic is a sibling leaf module

    slos = {
        n: SLOPolicy(deadline_ms=p["deadline_ms"], tier=p["tier"], name=p["name"])
        for n, p in payload.get("slos", {}).items()
    }
    m = ServeMetrics(
        list(payload.get("streams", {})),
        slos=slos or None,
        recent_window=payload.get("recent_window") or 64,
    )
    for name, st in payload.get("streams", {}).items():
        sm = m.streams[name]
        sm.latencies_s = [float(x) for x in st["latencies_s"]]
        sm.completed = int(st["completed"])
        sm.in_slo = int(st["in_slo"])
    for t, st in payload.get("tiers", {}).items():
        tm = m.tiers[int(t)] = TierMetrics(int(t))
        for f in ("offered", "admitted", "shed_res", "shed_route", "dropped",
                  "completed", "in_slo"):
            setattr(tm, f, int(st[f]))
        tm.latencies_s = [float(x) for x in st["latencies_s"]]
    m.ticks = [
        TickStats(
            int(row[0]), float(row[1]), float(row[2]), int(row[3]),
            engine_wait=(
                {n: tuple(float(x) for x in w) for n, w in row[4].items()}
                if len(row) > 4 and row[4] else None
            ),
        )
        for row in payload.get("ticks", [])
    ]
    m._recent.extend(bool(b) for b in payload.get("recent", []))
    m.batch_occupancy = {int(b): int(n) for b, n in payload.get("batch_occupancy", {}).items()}
    m.held_frames = int(payload.get("held_frames", 0))
    m.held_then_missed = int(payload.get("held_then_missed", 0))
    m.queue_wait_s = float(payload.get("queue_wait_s", 0.0))
    m.queue_frames = int(payload.get("queue_frames", 0))
    return m


# -- fleet aggregation -------------------------------------------------------


def router_imbalance(per_replica_counts) -> float:
    """Max/mean of per-replica routed-arrival counts: 1.0 is a perfectly
    balanced fleet; R means one replica took everything."""
    counts = list(per_replica_counts)
    if not counts:
        return math.nan
    mean = sum(counts) / len(counts)
    return max(counts) / mean if mean > 0 else 1.0


def merge_metrics(replica_metrics) -> "ServeMetrics":
    """Fold R replicas' per-replica ledgers into one fleet-level
    ``ServeMetrics``: stream latency samples concatenate, tier admission
    counters sum, and the tick log is pooled (fleet overlap efficiency is
    the replica aggregate). Streams are disjoint across replicas only in
    how traffic was routed — every replica declares the full stream set,
    so the union keys line up."""
    replica_metrics = list(replica_metrics)
    if not replica_metrics:
        raise ValueError("merge_metrics needs at least one replica")
    slos: dict = {}
    for m in replica_metrics:
        slos.update(m.slos)
    names: list[str] = []
    for m in replica_metrics:
        names.extend(n for n in m.streams if n not in names)
    agg = ServeMetrics(names, slos=slos or None)
    for m in replica_metrics:
        for name, sm in m.streams.items():
            a = agg.streams[name]
            a.latencies_s.extend(sm.latencies_s)
            a.completed += sm.completed
            a.in_slo += sm.in_slo
        for t, tm in m.tiers.items():
            at = agg.tiers.get(t)
            if at is None:
                at = agg.tiers[t] = TierMetrics(t)
            for f in ("offered", "admitted", "shed_res", "shed_route", "dropped",
                      "completed", "in_slo"):
                setattr(at, f, getattr(at, f) + getattr(tm, f))
            at.latencies_s.extend(tm.latencies_s)
        agg.ticks.extend(m.ticks)
        agg._recent.extend(m._recent)
        # batch occupancy merges across the fleet: histograms sum, so the
        # fleet report's mean effective batch is the frame-weighted mean
        for b, c in m.batch_occupancy.items():
            agg.batch_occupancy[b] = agg.batch_occupancy.get(b, 0) + c
        agg.held_frames += m.held_frames
        agg.held_then_missed += m.held_then_missed
        agg.queue_wait_s += m.queue_wait_s
        agg.queue_frames += m.queue_frames
    return agg


def fleet_report(replica_metrics, wall_s: float, routed_counts=None) -> dict:
    """Fleet-level serving report: the merged ledgers over one shared wall
    clock (replica FPS numbers do not sum — the fleet's throughput is
    total completions over the *fleet's* wall), plus the router-imbalance
    metric when per-replica routed-arrival counts are given."""
    rep = merge_metrics(replica_metrics).report(wall_s)
    rep["replicas"] = len(list(replica_metrics))
    if routed_counts is not None:
        rep["router_imbalance"] = router_imbalance(routed_counts)
    return rep
