"""Generic tick-based multi-stream executor with overlapped dispatch and
live plan hot-swap.

Generalizes the two-model HaX-CoNN swap pipeline: N staged models, each
with a planner-assigned route of ``PlanSegment``s (layer span + engine
binding), fed by K bounded per-stream frame queues. The executor consumes
*only* the typed ``core.plan_ir.PlanIR`` — scheduler results
(``NModelPlan``, ``HaxConnResult``) and legacy ``ModelRoute`` lists are
normalized to an IR at construction, and nothing downstream reaches into
scheduler internals. Plan spans are *layer* indices: on fine-granularity
(expanded-graph) models the ``StagedModel`` maps each span to its
sub-block stage executables (``op_spans``), so cuts inside composite
blocks stage and run exactly like coarse cuts — spans that don't land on
stage boundaries are rejected at staging time. One *tick* is one
steady-state cycle in two phases:

  * **issue** — every in-flight frame advances exactly one route segment
    (deepest stage first — the double-buffered counter-phase), then each
    model admits up to ``microbatch`` queued frames (round-robin over its
    streams) into stage 0. In the default ``dispatch="overlapped"`` mode
    the segment computations are only *dispatched* (JAX async dispatch):
    the host keeps issuing the other engines' segments while earlier ones
    compute, so counter-phased engines genuinely overlap. With
    ``jit_segments=True`` (the default) each (model, span) segment is
    additionally fused into one jitted executable — one dispatch per
    engine call instead of one per op — with the state buffers donated on
    backends that support donation, so a segment writes in place. XLA
    fusion may flip low-order bits vs the eager op sequence; pass
    ``jit_segments=False`` for the bit-exact-vs-``run_all`` baseline.
  * **resolve** — frames whose route finished are completed: the host
    blocks on the finalized outputs (the only synchronization point of
    the tick), stamps latencies, and splits merged or padded flights
    into their members' outputs with one compiled call (``split_flight``).

**Plan hot-swap** (the online re-planning runtime): ``swap_plan(new_ir)``
replaces the active plan at a frame boundary — between ticks, or at the
end of the tick that called it. Each flight snapshots its route at
admission, so in-flight frames finish on the plan they started under
while new admissions take the new routes: zero dropped frames, no
ordering change, and (routes being a pure re-orchestration of the same
op sequence) outputs equal to an unswapped run. ``prepare_plan(new_ir)``
pre-executes the new plan's segment executables on zero-filled states of
the shapes seen so far — the double-buffered staged-weights warmup that
keeps compilation off the hot path before the swap.

**Per-segment observation**: with ``profile_every=k``, every k-th tick is
a *profiled* tick — each segment call is individually synchronized and
its wall time recorded as a ``SegmentObservation`` (and pushed to the
``on_segment`` callback). That is the live cost feedback the
``serve.replanner`` folds into its ``OnlineCost`` EMA; non-profiled ticks
keep full overlap. ``segment_delay_fn`` injects an extra per-segment cost
on its engine (perturbation harness for the recovery benchmark): stalls
accrue per engine and the tick pays the slowest engine's total once,
overlapped with the async compute — a slowed *parallel* engine looks
exactly like this — while profiled observations report the engine-virtual
wall (compute + stall) so the drift detector sees the slowdown.

``dispatch="serialized"`` instead synchronizes after *every* segment
call — the pre-overlap behaviour kept as the measurable baseline. Both
modes run the exact same op sequence per frame as ``StagedModel.run_all``.
Per-tick host wall/blocked time is recorded in ``tick_stats`` (see
``metrics.TickStats.overlap_efficiency``) on every tick.

**Spans**: the executor records its work through ``tracer``, a
``serve.tracing.SpanRecorder`` (the server's, shared), which is off unless
enabled: ``executor.advance`` (in-flight segments, deepest first),
``executor.admit`` per model with child ``executor.stage_in`` (frame
upload, straight to the device of the route's first engine where engines
are bound to devices; donation copy; concatenation; padding),
``executor.dispatch`` per segment call with child ``executor.place``,
``executor.resolve`` per finished flight with children ``executor.block``
and, for a flight of several members or with pad lanes,
``executor.split``, and ``executor.on_tick``.
``TickStats.engine_wait`` is a view over the tick's ``dispatch``,
``place`` and ``block`` spans, present only while the recorder is on.
Every completion carries its frame's submit and admission stamps, so its
queue wait (admission minus submit, coalescer hold included) is always
known.

Micro-batching (``microbatch > 1``) admits up to that many same-model
frames per tick; with ``merge_batches`` the group is concatenated along
the leading axis and the route runs once for the merged state (only for
batch-independent models — see ``Pix2PixConfig(norm="instance")``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..core.pipeline import StagedModel, TickLog
from ..core.plan_ir import PlanIR, PlanSegment, ir_from_routes
from ..core.scheduler import NModelPlan
from .batching import BatchConfig
from .metrics import TickStats
from .streams import FrameQueue, StreamSpec
from .tracing import SpanRecorder

# engine_wait slot of each span that feeds it: (issue_s, transfer_s, resolve_s)
_WAIT_SLOT = {"executor.dispatch": 0, "executor.place": 1, "executor.block": 2}


@dataclasses.dataclass
class FlightMember:
    stream_index: int
    frame_id: int
    size: int  # leading-axis extent of this frame in the (possibly merged) state
    t_submit: float
    tick_submit: int
    degrade: int = 0  # admission degrade level (0 none, 1 resolution, 2 route)


@dataclasses.dataclass
class Flight:
    model_index: int
    members: list[FlightMember]
    state: Any
    stage: int  # segments already executed
    route: tuple[PlanSegment, ...]  # snapshot of the plan at admission
    revision: int  # plan revision the flight was admitted under
    degrade: int = 0  # level 2 flights run the degraded (single-segment) route
    valid: int = 0  # real frames in the (possibly padded) state; 0 = all
    bucket: int = 0  # padded leading-axis extent (the compiled bucket); 0 = valid
    held: bool = False  # the coalescer delayed this flight waiting for co-riders
    t_issue: float = 0.0  # admission wall clock (feeds the service-time EMA)
    t_admit: float = 0.0  # wall clock at which the members left their queues
    uid: int = 0  # the executor's flight number
    frames: str = ""  # members' ``<stream>/<frame id>`` keys, while tracing


@dataclasses.dataclass
class Completion:
    stream: str
    frame_id: int
    output: Any
    tick_submit: int
    tick_done: int
    latency_s: float  # wall-clock submit -> completion
    degrade: int = 0  # admission degrade level the frame ran under
    batch: int = 1  # real frames in the flight this frame rode in (occupancy)
    held: bool = False  # the flight was held by the coalescer before running
    t_submit: float = 0.0  # wall clock at submit
    t_admit: float = 0.0  # wall clock at which the frame left its queue for a flight
    t_done: float = 0.0  # wall clock at completion

    @property
    def queue_wait_s(self) -> float:
        """Submit to admission: time in the stream queue, coalescer hold included."""
        return self.t_admit - self.t_submit

    @property
    def service_s(self) -> float:
        """Admission to completion."""
        return self.t_done - self.t_admit


@dataclasses.dataclass(frozen=True)
class SegmentObservation:
    """One profiled segment execution — the executor's live cost signal."""

    tick: int
    model_index: int
    stage: int
    engine: int
    lo: int
    hi: int
    wall_s: float  # dispatch + sync wall time of this segment call
    batch: int  # leading-axis frames in the flight (merged groups > 1)
    revision: int  # plan revision the segment ran under
    impl: str = "xla"  # implementation variant the segment ran with
    bucket: int = 0  # padded bucket the segment executed at (0 = batch)


@dataclasses.dataclass(frozen=True)
class SwapEvent:
    tick: int
    revision: int
    partitions: tuple[int, ...]  # first cut per model (legacy view)
    expected_cycle: float
    cuts: tuple[tuple[int, ...], ...] = ()  # full k-cut vectors per model


def _leading(state) -> int:
    """Leading-axis extent of a state pytree (the executed batch bucket)."""
    leaves = jax.tree.leaves(state)
    if not leaves:
        return 1
    shape = jnp.shape(leaves[0]) if not hasattr(leaves[0], "shape") else leaves[0].shape
    return int(shape[0]) if shape else 1


def _upload(x, dev, donate: bool):
    """One leaf of a frame's initial state on ``dev``. A host array goes
    straight there in one upload, which makes a buffer of its own; so does
    a device array that lives elsewhere. Only a device array already on
    ``dev`` is copied, and only where segments donate their input state."""
    if isinstance(x, jax.Array) and x.devices() == {dev}:
        return jnp.copy(x) if donate else x
    return jax.device_put(x, dev)


@functools.partial(jax.jit, static_argnames="sizes")
def split_flight(out, sizes: tuple[int, ...]) -> tuple:
    """A finished flight's output split into its members' outputs, in one
    executable: member ``i`` gets every leaf sliced on axis 0 at ``[o, o +
    sizes[i])``, ``o`` the sizes before it, with static bounds. Lanes past
    ``sum(sizes)`` (a padded bucket's zero lanes) are in no member's slice.
    jit compiles once per (output shapes, ``sizes``)."""
    parts, o = [], 0
    for n in sizes:
        parts.append(jax.tree.map(lambda a: lax.slice_in_dim(a, o, o + n, axis=0), out))
        o += n
    return tuple(parts)


def _as_plan_ir(plan, engine_names=None) -> PlanIR:
    """Normalize every accepted plan form to the IR contract."""
    if isinstance(plan, PlanIR):
        return plan
    if isinstance(plan, NModelPlan):
        return plan.ir
    if hasattr(plan, "ir") and isinstance(getattr(plan, "ir"), PlanIR):
        return plan.ir  # HaxConnResult / Schedule
    return ir_from_routes(plan, engine_names=engine_names)


class StreamExecutor:
    """Drives N staged models over their planned routes for K streams."""

    def __init__(
        self,
        models: list[StagedModel],
        plan: PlanIR | NModelPlan | list,
        streams: list[StreamSpec],
        max_queue: int = 8,
        microbatch: int = 1,
        merge_batches: bool | list[bool] = False,
        place_fns: list[Callable] | None = None,
        engine_names: list[str] | None = None,
        model_labels: list[str] | None = None,
        dispatch: str = "overlapped",
        jit_segments: bool = True,
        profile_every: int = 0,
        on_segment: Callable[[SegmentObservation], None] | None = None,
        segment_delay_fn: Callable[[PlanSegment], float] | None = None,
        batching: BatchConfig | None = None,
        engine_params: list[list] | None = None,
        tracer: SpanRecorder | None = None,
        engine_devices: list | None = None,
    ):
        ir = _as_plan_ir(plan, engine_names)
        if len(models) != ir.n_models:
            raise ValueError(f"{len(models)} models but plan routes {ir.n_models}")
        ir.validate_against([m.n_layers for m in models])
        self._check_span_staging(ir, models)
        for s in streams:
            if not 0 <= s.model_index < len(models):
                raise ValueError(f"stream {s.name} references unknown model {s.model_index}")
        if microbatch < 1:
            raise ValueError("microbatch must be >= 1")
        if dispatch not in ("overlapped", "serialized"):
            raise ValueError(f"dispatch must be 'overlapped' or 'serialized', got {dispatch!r}")
        if profile_every < 0:
            raise ValueError("profile_every must be >= 0 (0 = no segment profiling)")
        self.models = models
        self.plan = ir
        self.streams = streams
        self.microbatch = microbatch
        self.dispatch = dispatch
        if isinstance(merge_batches, bool):
            self.merge_batches = [merge_batches] * len(models)
        else:
            if len(merge_batches) != len(models):
                raise ValueError(f"{len(merge_batches)} merge flags but {len(models)} models")
            self.merge_batches = list(merge_batches)
        n_engines = ir.n_engines
        self.place_fns = place_fns or [lambda x: x] * n_engines
        # per-engine weight copies on the engine's device (``DevicePool.
        # place_params``); None = every engine runs on the models' params
        self.engine_params = engine_params
        # each engine's device (``DevicePool.engine_devices``): stage-in
        # uploads a frame straight to its route's first engine; None = one
        # device, where frames go wherever JAX puts them by default
        self.engine_devices = engine_devices
        self.engine_names = list(engine_names) if engine_names else list(ir.engine_names)
        self.model_labels = model_labels or [m.name for m in models]
        self.queues = [FrameQueue(max_queue) for _ in streams]
        self.in_flight: list[Flight] = []
        self.completions: list[Completion] = []
        self.outputs: dict[str, list] = {s.name: [] for s in streams}
        self.log: list[TickLog] = []
        self.tick_stats: list[TickStats] = []
        self.tick_count = 0
        self._frame_ids = [0] * len(streams)
        self._rr = [0] * len(models)  # round-robin cursor per model
        self._streams_of = [
            [i for i, s in enumerate(streams) if s.model_index == m] for m in range(len(models))
        ]
        self._blocked_s = 0.0  # block_until_ready time inside the current tick
        self._segments_issued = 0
        # live cost feedback + re-planning hooks
        self.profile_every = profile_every
        self.on_segment = on_segment
        self.on_tick: Callable[["StreamExecutor"], None] | None = None
        self.segment_delay_fn = segment_delay_fn
        self._tick_delay: dict[int, float] = {}  # engine -> accrued stall this tick
        self.segment_obs: list[SegmentObservation] = []
        self.swap_events: list[SwapEvent] = []
        self._profiling_tick = False
        # stage-0 state structs seen per model (for prepare_plan warmups)
        self._state_structs: dict[int, list] = {m: [] for m in range(len(models))}
        self.jit_segments = jit_segments
        # donation needs backend support; the CPU client ignores donated
        # buffers (and warns), so only donate segment state buffers off-CPU
        self._donate = jax.default_backend() not in ("cpu",)
        # keyed by (model, lo, hi, impl, bucket): hot-swapped plans whose
        # spans (and implementation bindings) coincide with an old plan's
        # reuse the same (possibly compiled) runner; the bucket key gives
        # every batch size its own warmed executable so steady-state
        # batched serving never recompiles
        self._seg_fns: dict[tuple[int, int, int, str, int], Callable] = {}
        # degraded single-segment routes, keyed (model, plan revision)
        self._degraded_routes: dict[tuple[int, int], tuple[PlanSegment, ...]] = {}
        # per-model stream admission order: strictly tier-first (round-robin
        # within a tier); identical to plain round-robin when no stream
        # carries an SLO, so closed-loop behaviour is unchanged
        self._tiers = [s.tier for s in streams]
        # continuous batching (coalescer) state
        self.batching = batching or BatchConfig()
        self._hold_since: dict[int, float] = {}  # model -> wall clock hold start
        self._held_pending: set[int] = set()  # models with a hold in progress
        # observed admission->completion service time EMA per (model, bucket):
        # the self-calibrating "expected batched segment time" the hold
        # decision compares slack against
        self._svc_ema: dict[tuple[int, int], float] = {}
        self.tracer = tracer if tracer is not None else SpanRecorder()
        self._flights = 0  # flights admitted so far (their uids)

    # -- submission ---------------------------------------------------------

    def submit(self, stream: int | str, frame: Any, degrade: int = 0) -> bool:
        """Queue a frame on a stream; False = queue full (backpressure).

        ``degrade`` is the admission controller's degrade level: level-1
        frames were resolution-shed upstream (they only opt out of merge
        batching — their shapes differ), level-2 frames run the degraded
        single-segment route instead of the plan's."""
        si = stream if isinstance(stream, int) else self._stream_index(stream)
        fid = self._frame_ids[si]
        if not self.queues[si].push((fid, frame, time.perf_counter(), degrade)):
            return False
        self._frame_ids[si] += 1
        return True

    def _stream_index(self, name: str) -> int:
        for i, s in enumerate(self.streams):
            if s.name == name:
                return i
        raise KeyError(f"unknown stream {name!r}")

    @property
    def pending(self) -> int:
        return sum(len(q) for q in self.queues) + sum(len(f.members) for f in self.in_flight)

    def queue_pressure(self, model_index: int | None = None) -> float:
        """Aggregate queue fill fraction in [0, 1] — the admission
        controller's and re-planner's load signal. Restricted to one
        model's streams when ``model_index`` is given."""
        qs = [
            q
            for si, q in enumerate(self.queues)
            if model_index is None or self.streams[si].model_index == model_index
        ]
        cap = sum(q.maxdepth for q in qs)
        return sum(len(q) for q in qs) / cap if cap else 0.0

    # -- plan hot-swap ------------------------------------------------------

    @property
    def plan_revision(self) -> int:
        return self.plan.revision

    @staticmethod
    def _check_span_staging(ir: PlanIR, models):
        """Reject plans whose spans can't stage before any frame runs:
        on fine-granularity models every route segment — however many
        cuts the plan takes — must start and end on stage-callable
        boundaries (``StagedModel.check_route``)."""
        for mi, segs in enumerate(ir.segments):
            models[mi].check_route([(s.lo, s.hi) for s in segs])

    def swap_plan(self, new_ir: PlanIR) -> int:
        """Install a new plan at the next frame boundary (new admissions).

        In-flight frames keep their admission-time route snapshots, so the
        swap drops nothing and changes no frame's op sequence — only where
        future segments run. Returns the new plan revision.
        """
        if tuple(new_ir.models) != tuple(self.plan.models):
            raise ValueError(
                f"swap changes the model set {self.plan.models} -> {new_ir.models}"
            )
        if new_ir.n_engines > len(self.place_fns):
            raise ValueError(
                f"swap needs {new_ir.n_engines} engines but executor has {len(self.place_fns)}"
            )
        new_ir.validate_against([m.n_layers for m in self.models])
        self._check_span_staging(new_ir, self.models)
        rev = self.plan.revision + 1
        self.plan = new_ir.with_revision(rev)
        self.swap_events.append(
            SwapEvent(
                tick=self.tick_count,
                revision=rev,
                partitions=tuple(new_ir.partitions),
                expected_cycle=new_ir.expected_cycle,
                cuts=new_ir.cuts,
            )
        )
        self.log.append(TickLog(self.tick_count, "*", f"swap->rev{rev} cuts={list(new_ir.cuts)}"))
        return rev

    def prepare_plan(self, new_ir: PlanIR) -> int:
        """Warm the new plan's segment executables off the hot path.

        For every stage-0 state shape seen so far, abstractly threads the
        state through the new routes and runs each segment once on zeros —
        seeding the jit caches (double-buffered executables: the old
        plan's stay valid for in-flight frames). Returns the number of
        segment executions warmed; silently skips models that have not
        seen a frame yet.
        """
        new_ir.validate_against([m.n_layers for m in self.models])
        self._check_span_staging(new_ir, self.models)
        warmed = 0
        for mi, segs in enumerate(new_ir.segments):
            model = self.models[mi]
            for _, struct in self._state_structs[mi]:
                for bstruct in self._warm_structs(mi, struct):
                    bucket = _leading(bstruct)
                    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), bstruct)
                    for seg in segs:
                        impl = getattr(seg, "impl", "xla")
                        key = (mi, seg.lo, seg.hi, impl, bucket)
                        if key not in self._seg_fns:
                            self._seg_fns[key] = self._make_runner(mi, seg.lo, seg.hi, impl)
                        state = self.place_fns[seg.engine](state)
                        state = self._seg_fns[key](self._params(mi, seg.engine), state)
                        warmed += 1
                    jax.block_until_ready(state)
        return warmed

    def _warm_structs(self, mi: int, struct):
        """The state structs a plan warmup must compile for: the seen
        struct itself plus — for models the coalescer may batch — every
        bucket-scaled variant of its single-frame shapes, so a plan swap
        lands with all bucket executables warm and steady-state batched
        serving never compiles on the hot path."""
        out = [struct]
        bc = self.batching
        if bc.enabled and self.merge_batches[mi] and _leading(struct) == 1:
            for b in bc.buckets:
                if b == 1:
                    continue
                out.append(
                    jax.tree.map(
                        lambda s, b=b: jax.ShapeDtypeStruct((b,) + tuple(s.shape[1:]), s.dtype),
                        struct,
                    )
                )
        return out

    # -- execution ----------------------------------------------------------

    def _block(self, x, engine: int | None = None):
        """block_until_ready with the wait charged to this tick's stats
        (and, while tracing, to an ``executor.block`` span on ``engine``)."""
        with self.tracer.span("executor.block", engine=self._engine_name(engine)):
            t0 = time.perf_counter()
            x = jax.block_until_ready(x)
            self._blocked_s += time.perf_counter() - t0
        return x

    def _engine_name(self, engine: int | None) -> str:
        return "" if engine is None else self.engine_names[engine]

    def _engine_wait(self, mark: int) -> dict | None:
        """Per-engine ``(issue_s, transfer_s, resolve_s)`` of the spans
        ended since ``mark``: ``executor.dispatch`` self time (the segment
        call without its placement), ``executor.place`` and
        ``executor.block``. None while the recorder is off."""
        if not self.tracer.enabled:
            return None
        acc: dict[str, list[float]] = {}
        for s in self.tracer.since(mark):
            slot = _WAIT_SLOT.get(s.name)
            engine = s.attrs.get("engine")
            if slot is None or not engine:
                continue
            a = acc.setdefault(engine, [0.0, 0.0, 0.0])
            a[slot] += s.self_s if slot == 0 else s.dur
        return {e: tuple(a) for e, a in acc.items()} or None

    def _params(self, mi: int, engine: int):
        """Model ``mi``'s params as placed for ``engine``."""
        if self.engine_params is None:
            return self.models[mi].params
        return self.engine_params[engine][mi]

    def _make_runner(self, mi: int, lo: int, hi: int, impl: str = "xla") -> Callable:
        model = self.models[mi]
        if self.jit_segments:
            # cached on the model: executors over the same span share one
            # compiled executable per (segment, impl, shape)
            return model.jitted_segment_fn(lo, hi, donate=self._donate, impl=impl)
        return model.segment_fn(lo, hi, impl=impl)

    def _degraded_route(self, mi: int) -> tuple[PlanSegment, ...]:
        """The model's shed-staging route: the whole layer span as one
        coarse segment on the engine already carrying most of its planned
        work (fewest hand-offs, no inter-engine transfers — the minimum
        service-time fallback admission control escalates to). Always
        stage-legal: [0, n_layers) starts and ends on stage boundaries."""
        key = (mi, self.plan.revision)
        route = self._degraded_routes.get(key)
        if route is None:
            segs = self.plan.route(mi)
            load: dict[int, float] = {}
            for s in segs:
                load[s.engine] = load.get(s.engine, 0.0) + s.expected_cost
            eng = max(load, key=lambda e: (load[e], -e))
            route = (
                PlanSegment(
                    model_index=mi,
                    stage=0,
                    engine=eng,
                    lo=0,
                    hi=segs[-1].hi,
                    expected_cost=sum(s.expected_cost for s in segs),
                ),
            )
            self._degraded_routes[key] = route
        return route

    def _segment_runner(self, mi: int, seg: PlanSegment, bucket: int = 1) -> Callable:
        impl = getattr(seg, "impl", "xla")
        key = (mi, seg.lo, seg.hi, impl, bucket)
        fn = self._seg_fns.get(key)
        if fn is None:
            fn = self._make_runner(mi, seg.lo, seg.hi, impl)
            self._seg_fns[key] = fn
        return fn

    def _run_segment(self, flight: Flight):
        """Issue one route segment for a flight. In overlapped mode this
        only dispatches the computation (async); serialized mode waits for
        it. Profiled ticks synchronize per segment to stamp a wall-time
        observation (the live cost feedback)."""
        seg = flight.route[flight.stage]
        eng = seg.engine
        mi = flight.model_index
        t0 = time.perf_counter() if self._profiling_tick else 0.0
        tr = self.tracer
        with tr.span("executor.dispatch", model=self.model_labels[mi], lo=seg.lo, hi=seg.hi,
                     engine=self.engine_names[eng], bucket=flight.bucket, flight=flight.uid,
                     frames=flight.frames):
            with tr.span("executor.place", engine=self.engine_names[eng]):
                state = self.place_fns[eng](flight.state)
            bucket = flight.bucket or flight.valid or _leading(state)
            flight.state = self._segment_runner(mi, seg, bucket)(self._params(mi, eng), state)
        d = 0.0
        if self.segment_delay_fn is not None:
            d = self.segment_delay_fn(seg)
            if d > 0:
                # simulated engine slowdown: engines stall concurrently on
                # real hardware, so the stall accrues to this engine's
                # per-tick total (paid as max over engines at tick end)
                # instead of sleeping inline, which would serialize
                # stalls that genuinely overlap
                self._tick_delay[eng] = self._tick_delay.get(eng, 0.0) + d
        flight.stage += 1
        self._segments_issued += 1
        ids = ",".join(str(m.frame_id) for m in flight.members)
        self.log.append(
            TickLog(
                self.tick_count,
                self.engine_names[eng],
                f"{self.model_labels[flight.model_index]}[{seg.lo}:{seg.hi})#f{ids}",
            )
        )
        if self._profiling_tick:
            self._block(flight.state, engine=eng)
            obs = SegmentObservation(
                tick=self.tick_count,
                model_index=flight.model_index,
                stage=seg.stage,
                engine=eng,
                lo=seg.lo,
                hi=seg.hi,
                # the engine-virtual wall: what this span costs on its
                # (possibly slowed) engine
                wall_s=time.perf_counter() - t0 + d,
                batch=sum(m.size for m in flight.members),
                revision=flight.revision,
                impl=getattr(seg, "impl", "xla"),
                bucket=bucket,
            )
            self.segment_obs.append(obs)
            if self.on_segment is not None:
                self.on_segment(obs)
        elif self.dispatch == "serialized":
            self._block(flight.state, engine=eng)

    def _complete(self, flight: Flight):
        """Block on a finished flight's output, stamp its completion time,
        and hand each member its output: a single unpadded flight's output
        as it is, any other flight's through one ``split_flight`` call over
        ``[0, valid)``, which leaves the pad lanes out."""
        model = self.models[flight.model_index]
        last_eng = flight.route[-1].engine if flight.route else None
        out = self._block(model.finalize(flight.state), engine=last_eng)
        now = time.perf_counter()
        valid = flight.valid or sum(m.size for m in flight.members)
        if flight.t_issue:
            # fold this flight's admission->completion wall into the
            # per-(model, bucket) service EMA the coalescer's hold
            # decision consults
            key = (flight.model_index, flight.bucket or valid)
            svc = now - flight.t_issue
            prev = self._svc_ema.get(key)
            self._svc_ema[key] = svc if prev is None else 0.7 * prev + 0.3 * svc
        if len(flight.members) == 1 and not (flight.bucket and flight.bucket > valid):
            sliced = [out]
        else:
            # padded lanes (bucket > valid) fall off here: the split covers
            # only [0, valid), so the zero-filled pad rows are never
            # observable in any completion — bit-exactness vs per-frame
            # execution is a slicing invariant, not a masking op
            with self.tracer.span("executor.split", flight=flight.uid,
                                  members=len(flight.members), bucket=flight.bucket or valid):
                sliced = split_flight(out, tuple(m.size for m in flight.members))
        for m, o in zip(flight.members, sliced):
            name = self.streams[m.stream_index].name
            self.outputs[name].append(o)
            self.completions.append(
                Completion(
                    stream=name,
                    frame_id=m.frame_id,
                    output=o,
                    tick_submit=m.tick_submit,
                    tick_done=self.tick_count,
                    latency_s=now - m.t_submit,
                    degrade=m.degrade,
                    batch=valid,
                    held=flight.held,
                    t_submit=m.t_submit,
                    t_admit=flight.t_admit,
                    t_done=now,
                )
            )

    def _note_state_struct(self, mi: int, state):
        struct = jax.tree.map(lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), state)
        flat, treedef = jax.tree.flatten(struct)
        key = (treedef, tuple((s.shape, s.dtype) for s in flat))
        known = self._state_structs[mi]
        if key not in [k for k, _ in known]:
            known.append((key, struct))

    def expected_service(self, mi: int, bucket: int) -> float:
        """Observed admission->completion wall EMA for (model, bucket) —
        the coalescer's self-calibrating estimate of what riding a batch
        of that size costs. Falls back to the largest smaller bucket seen
        (batched service is monotone-ish in bucket), 0.0 before any
        observation (hold decisions then bound only by the hold window)."""
        t = self._svc_ema.get((mi, bucket))
        if t is not None:
            return t
        seen = [b for (m, b), _ in self._svc_ema.items() if m == mi and b < bucket]
        return self._svc_ema[(mi, max(seen))] if seen else 0.0

    def _should_hold(self, mi: int, cands: list[tuple[int, tuple]], now: float) -> bool:
        """The slack-driven hold decision for a partial bucket: wait for
        co-riders only when *every* waiting member's SLO slack clears the
        expected batched service time (scaled by ``min_slack_factor``)
        plus the full hold window — so a hold can never turn a meetable
        deadline into a miss — and the hold window has not expired. Any
        degraded candidate or an empty window admits immediately (under
        queue pressure the caller has already filled the bucket, so high
        load never holds and batching never costs goodput)."""
        bc = self.batching
        if bc.hold_s <= 0.0:
            return False
        started = self._hold_since.get(mi)
        if started is not None and now - started >= bc.hold_s:
            return False  # window expired: admit what we have
        if any(item[3] > 0 for _, item in cands):
            return False  # degraded frames never wait on a merge they can't join
        total = sum(
            int(item[1].shape[0]) if hasattr(item[1], "shape") and item[1].shape else 1
            for _, item in cands
        )
        t_b = self.expected_service(mi, bc.bucket_for(total))
        floor = bc.min_slack_factor * t_b + bc.hold_s
        for si, item in cands:
            slo = self.streams[si].slo
            if slo is None:
                continue
            slack = slo.deadline_s - (now - item[2])
            if slack <= floor:
                return False
        return True

    def _admit(self, mi: int) -> list[Flight]:
        """Admit queued frames for model ``mi`` into stage 0 of the
        *current* plan; returns the flights that already finished their
        route (single-segment models). Streams are drained strictly
        tier-first (SLO priority); within a tier the oldest waiting head
        goes first (age tiebreak — a stream can no longer lose the
        microbatch cut forever to rotation phasing), falling back to
        round-robin order on equal ages. With no SLOs attached every tier
        is 0 and fresh frames tie, so closed-loop behaviour is unchanged.

        With an enabled ``BatchConfig`` and a batch-independent model
        (``merge_batches``), admission becomes the cross-stream
        coalescer: up to ``max_batch`` clean frames from any of the
        model's streams merge into one flight, padded to the power-of-two
        bucket; a partial bucket may *hold* (frames stay queued) while
        every member's slack allows it — see ``_should_hold``."""
        stream_idxs = self._streams_of[mi]
        if not stream_idxs:
            return []
        bc = self.batching
        coalesce = bc.enabled and self.merge_batches[mi]
        cap = bc.max_batch if coalesce else self.microbatch
        n = len(stream_idxs)
        start = self._rr[mi]
        rotated = [stream_idxs[(start + k) % n] for k in range(n)]
        now = time.perf_counter()

        def head_age(si: int) -> float:
            q = self.queues[si]
            return now - q.peek()[2] if len(q) else -1.0

        # stable: (tier, oldest-head-first), rr order breaking exact ties
        rotated.sort(key=lambda si: (self._tiers[si], -head_age(si)))
        # candidate collection peeks without popping: a held bucket's
        # frames must stay queued (and keep aging) until admission.
        # Coalescing drains multiple frames per stream (greedy bucket
        # fill under queue pressure); classic admission keeps the one-
        # frame-per-stream round-robin cut.
        cands: list[tuple[int, tuple]] = []
        if coalesce:
            pos = {si: 0 for si in rotated}
            progress = True
            while len(cands) < cap and progress:
                progress = False
                for si in rotated:
                    if len(cands) >= cap:
                        break
                    if pos[si] < len(self.queues[si]):
                        cands.append((si, self.queues[si].peek(pos[si])))
                        pos[si] += 1
                        progress = True
        else:
            for si in rotated:
                if len(cands) >= cap:
                    break
                if len(self.queues[si]):
                    cands.append((si, self.queues[si].peek()))
        if not cands:
            return []
        held = mi in self._held_pending
        if coalesce and len(cands) < cap and self._should_hold(mi, cands, now):
            if mi not in self._hold_since:
                self._hold_since[mi] = now
            self._held_pending.add(mi)
            return []
        self._hold_since.pop(mi, None)
        self._held_pending.discard(mi)
        picked: list[tuple[int, int, Any, float, int]] = []
        for si, _ in cands:
            fid, frame, t_sub, degrade = self.queues[si].pop()
            picked.append((si, fid, frame, t_sub, degrade))
        self._rr[mi] = (start + len(picked)) % n
        with self.tracer.span("executor.stage_in") as sp:
            flights = self._stage_in(mi, picked, held, coalesce)
            for flight in flights:
                flight.t_admit = now
            if self.tracer.enabled:
                sp.note(flight=";".join(str(f.uid) for f in flights),
                        frames=";".join(f.frames for f in flights),
                        bucket=";".join(str(f.bucket) for f in flights))
        done = []
        for flight in flights:
            self._run_segment(flight)
            if flight.stage == len(flight.route):
                done.append(flight)
            else:
                self.in_flight.append(flight)
        return done

    def _stage_in(self, mi: int, picked: list, held: bool, coalesce: bool) -> list[Flight]:
        """The flights of the frames ``picked`` off model ``mi``'s queues:
        each frame's initial state on the device (with ``engine_devices``,
        uploaded straight to its route's first engine's device; copied
        where segments donate and the upload made no buffer of its own),
        clean frames concatenated and padded to their bucket where the
        model merges, degraded frames in flights of their own."""
        model = self.models[mi]
        bc = self.batching
        members, states = [], []
        route = self.plan.route(mi)
        rev = self.plan.revision
        for si, fid, frame, t_sub, degrade in picked:
            size = int(frame.shape[0]) if hasattr(frame, "shape") and frame.shape else 1
            members.append(FlightMember(si, fid, size, t_sub, self.tick_count, degrade=degrade))
            state = model.init_state(frame)
            if self.engine_devices is not None:
                first = (self._degraded_route(mi) if degrade >= 2 else route)[0].engine
                dev = self.engine_devices[first]
                state = jax.tree.map(lambda x: _upload(x, dev, self._donate), state)
            elif self._donate:
                # segments donate their input state: never the caller's frame
                state = jax.tree.map(jnp.copy, state)
            states.append(state)
        # Degraded frames never merge: level-1 frames have shed shapes,
        # level-2 frames run the degraded route, both incompatible with a
        # concatenated full-route group.
        clean = [(m, s) for m, s in zip(members, states) if m.degrade == 0]
        shed = [(m, s) for m, s in zip(members, states) if m.degrade > 0]
        if self.merge_batches[mi] and len(clean) > 1:
            merged = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *(s for _, s in clean))
            total = sum(m.size for m, _ in clean)
            bucket = bc.bucket_for(total) if coalesce else total
            if bucket > total:
                # pad to the compiled bucket with zero lanes; _complete
                # slices members out of [0, total) so the pads are never
                # observable (bit-exact vs per-frame execution)
                dev = None if self.engine_devices is None else self.engine_devices[route[0].engine]
                merged = jax.tree.map(
                    lambda a: jnp.concatenate(
                        [a, jnp.zeros((bucket - total,) + a.shape[1:], a.dtype, device=dev)], axis=0
                    ),
                    merged,
                )
            flights = [
                Flight(
                    model_index=mi,
                    members=[m for m, _ in clean],
                    state=merged,
                    stage=0,
                    route=route,
                    revision=rev,
                    valid=total,
                    bucket=bucket,
                    held=held,
                )
            ]
        else:
            flights = [
                Flight(
                    model_index=mi,
                    members=[m],
                    state=s,
                    stage=0,
                    route=route,
                    revision=rev,
                    valid=m.size,
                    bucket=m.size,
                    held=held and m.degrade == 0,
                )
                for m, s in clean
            ]
        for m, s in shed:
            flights.append(
                Flight(
                    model_index=mi,
                    members=[m],
                    state=s,
                    stage=0,
                    route=self._degraded_route(mi) if m.degrade >= 2 else route,
                    revision=rev,
                    degrade=m.degrade,
                    valid=m.size,
                    bucket=m.size,
                )
            )
        for flight in flights:
            self._flights += 1
            flight.uid = self._flights
            if self.tracer.enabled:
                flight.frames = ";".join(
                    f"{self.streams[m.stream_index].name}/{m.frame_id}" for m in flight.members
                )
            self._note_state_struct(mi, flight.state)
            flight.t_issue = time.perf_counter()
        return flights

    def tick(self):
        """One steady-state cycle. Issue phase: advance every in-flight
        frame one segment (deepest first), then admit new frames into
        stage 0 — all dispatched without waiting in overlapped mode.
        Resolve phase: block on (only) the frames whose route finished."""
        t_start = time.perf_counter()
        tr = self.tracer
        mark = tr.recorded
        self._blocked_s = 0.0
        self._segments_issued = 0
        self._profiling_tick = self.profile_every > 0 and self.tick_count % self.profile_every == 0
        if self._profiling_tick and self.in_flight:
            # drain the async dispatch queue before timing anything: without
            # this barrier the first profiled segment absorbs the previous
            # tick's in-flight work and its wall time is attributed to the
            # wrong (model, engine, span) — poisoning the cost calibration
            for f in self.in_flight:
                last = f.route[min(f.stage, len(f.route) - 1)].engine if f.route else None
                self._block(f.state, engine=last)
        done: list[Flight] = []
        # deepest stage first; route lengths may differ across plan
        # revisions, so the depth bound comes from the live flights
        max_stages = max((len(f.route) for f in self.in_flight), default=1)
        with tr.span("executor.advance"):
            for stage in range(max_stages - 1, 0, -1):
                for mi in range(len(self.models)):
                    for flight in [
                        f for f in self.in_flight if f.model_index == mi and f.stage == stage
                    ]:
                        self._run_segment(flight)
                        if flight.stage == len(flight.route):
                            done.append(flight)
                            self.in_flight.remove(flight)
        for mi in range(len(self.models)):
            with tr.span("executor.admit", model=self.model_labels[mi]):
                done.extend(self._admit(mi))
        if self._tick_delay:
            # pay the slowest engine's accrued stall once per tick, before
            # resolving: concurrent engines' stalls overlap each other and
            # the still-async dispatched compute
            time.sleep(max(self._tick_delay.values()))
            self._tick_delay.clear()
        for flight in done:
            with tr.span("executor.resolve", model=self.model_labels[flight.model_index],
                         flight=flight.uid, frames=flight.frames):
                self._complete(flight)
        self.tick_stats.append(
            TickStats(
                tick=self.tick_count,
                wall_s=time.perf_counter() - t_start,
                blocked_s=self._blocked_s,
                segments=self._segments_issued,
                engine_wait=self._engine_wait(mark),
            )
        )
        self.tick_count += 1
        if self.on_tick is not None:
            # frame boundary: the replanner's chance to observe drift and
            # hot-swap before the next admission
            with tr.span("executor.on_tick"):
                self.on_tick(self)

    def run_until_drained(self, max_ticks: int = 100000):
        while self.pending:
            if self.tick_count >= max_ticks:
                raise RuntimeError(f"executor did not drain within {max_ticks} ticks")
            self.tick()
        return self.outputs

    def overlap_efficiency(self) -> float:
        """Aggregate fraction of tick time the host was not blocked."""
        from .metrics import overlap_summary

        return overlap_summary(self.tick_stats)["overlap_efficiency"]
