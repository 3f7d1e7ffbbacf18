"""Concurrent two-model pipeline executor (the paper's DeepStream analogue).

A ``StagedModel`` wraps per-layer executable ops aligned with the model's
``LayerGraph``. ``TwoModelPipeline`` executes a HaX-CoNN swap schedule in
steady state with double buffering:

  tick t:  E_con runs A[0:pa) of frame t      E_flex runs B[0:pb) of frame t
           E_con runs B[pb:)  of frame t-1    E_flex runs A[pa:)  of frame t-1

On real hardware the two engines are disjoint device sets and the four
segment calls are dispatched asynchronously (JAX's async dispatch overlaps
them); on this CPU container they serialize but remain functionally
identical, which is what the correctness tests pin down. ``place_fn``
hooks engine-boundary transfers (``jax.device_put`` to a submesh on TPU).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax

from .graph import LayerGraph
from .plan_ir import PlanIR


def executable_label(model_name: str) -> str:
    """A model's name with every run of characters other than letters,
    digits and ``_`` made one ``_``: the first part of its segment
    executables' names."""
    return re.sub(r"[^0-9A-Za-z_]+", "_", model_name).strip("_")


@dataclasses.dataclass
class StagedModel:
    name: str
    ops: list[tuple[str, Callable]]  # (name, fn(params, state) -> state)
    params: Any
    graph: LayerGraph
    init_state: Callable[[Any], dict]
    finalize: Callable[[dict], Any]
    # per-frame outputs independent of batch companions (instance/group
    # norm) — the precondition for merge_batches micro-batching
    batch_independent: bool = False
    # layer span [lo, hi) each op covers when the graph is finer than the
    # op list (expanded graphs: one op per *stage callable*, several
    # primitive layers per op). None = ops align 1:1 with graph layers.
    op_spans: list[tuple[int, int]] | None = None
    # named implementation variants: impl -> same-length op list (e.g.
    # "pallas_fused" with each fused block collapsed onto its lead op), and
    # the op-index groups [a, b) that must be substituted atomically — a
    # group only switches impl when a segment contains it entirely, so cut
    # points interior to a fused block keep the reference ops
    variant_ops: dict[str, list[tuple[str, Callable]]] | None = None
    variant_groups: list[tuple[int, int]] | None = None

    def __post_init__(self):
        for impl, vops in (self.variant_ops or {}).items():
            assert len(vops) == len(self.ops), (
                f"{self.name}: variant {impl!r} has {len(vops)} ops, expected {len(self.ops)}"
            )
        if self.op_spans is None:
            assert len(self.ops) == len(self.graph), (
                f"{self.name}: ops ({len(self.ops)}) must align with layer graph ({len(self.graph)})"
            )
        else:
            assert len(self.op_spans) == len(self.ops), (
                f"{self.name}: {len(self.op_spans)} op spans for {len(self.ops)} ops"
            )
            pos = 0
            for lo, hi in self.op_spans:
                assert lo == pos and hi > lo, f"{self.name}: op spans must partition the graph"
                pos = hi
            assert pos == len(self.graph), (
                f"{self.name}: op spans cover [0,{pos}) but the graph has {len(self.graph)} layers"
            )
            self._op_start = {lo: i for i, (lo, _) in enumerate(self.op_spans)}
            self._op_end = {hi: i + 1 for i, (_, hi) in enumerate(self.op_spans)}

    @property
    def n_layers(self) -> int:
        """Layer count of the planning graph — the unit PlanIR spans use."""
        return len(self.graph)

    def op_range(self, lo, hi) -> tuple[int, int]:
        """Map a layer span [lo, hi) to the op range that executes it.

        With ``op_spans`` the span must start and end on stage-callable
        boundaries — exactly the cuts ``LayerGraph.cut_points`` declares
        legal; anything else raises."""
        if self.op_spans is None:
            return lo, hi
        try:
            return self._op_start[lo], self._op_end[hi]
        except KeyError:
            raise ValueError(
                f"{self.name}: layer span [{lo},{hi}) does not align with stage boundaries"
            ) from None

    def run_segment(self, state, lo, hi, impl: str = "xla"):
        return self.segment_fn(lo, hi, impl)(self.params, state)

    def segment_ops(self, lo, hi, impl: str = "xla"):
        """The (name, fn) ops executing layers ``[lo, hi)`` under ``impl``.

        Variant substitution is per fused group and only where the group's
        op span [a, b) lies entirely inside the segment; everything else —
        including blocks split by the segment boundary — stays ``xla``."""
        olo, ohi = self.op_range(lo, hi)
        ops = list(self.ops[olo:ohi])
        vops = (self.variant_ops or {}).get(impl)
        if impl != "xla" and vops is not None:
            for a, b in self.variant_groups or []:
                if a >= olo and b <= ohi:
                    ops[a - olo : b - olo] = vops[a:b]
        return ops

    def segment_fn(self, lo, hi, impl: str = "xla"):
        """Pure ``(params, state) -> state`` over the ops executing layers
        ``[lo, hi)`` — the form ``jax.jit`` (with state-buffer donation)
        accepts. Each op runs under ``jax.named_scope(<op name>)``, so a
        device trace names the op each operation came from; the function is
        named ``segment_name(lo, hi, impl)``."""
        ops = self.segment_ops(lo, hi, impl)

        def f(params, state):
            for name, fn in ops:
                with jax.named_scope(name):
                    state = fn(params, state)
            return state

        f.__name__ = f.__qualname__ = self.segment_name(lo, hi, impl)
        return f

    def segment_name(self, lo, hi, impl: str = "xla") -> str:
        """``<label>.<lo>_<hi>.<impl>`` (``executable_label`` of the model's
        name): a segment executable compiles as ``jit_<this>``, stable
        across rebuilds and distinct per segment."""
        return f"{executable_label(self.name)}.{lo}_{hi}.{impl}"

    def jitted_segment_fn(self, lo, hi, donate: bool = False, impl: str = "xla"):
        """Fused one-executable form of ``segment_fn``, cached on the model
        so every executor over the same route shares the compilation."""
        if not hasattr(self, "_jit_cache"):
            self._jit_cache = {}
        key = (lo, hi, donate, impl)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                self.segment_fn(lo, hi, impl), donate_argnums=(1,) if donate else ()
            )
        return self._jit_cache[key]

    def check_route(self, spans) -> None:
        """Validate that an arbitrary span list tiles [0, n_layers) on
        stage-executable boundaries — the staging precondition for a
        k-segment route. Raises ``ValueError`` with the offending span
        otherwise (gaps, overlaps, short coverage, or a cut inside a
        fused stage callable)."""
        pos = 0
        for lo, hi in spans:
            if lo != pos or hi <= lo:
                raise ValueError(
                    f"{self.name}: route spans must tile the graph contiguously; "
                    f"got [{lo},{hi}) at layer {pos}"
                )
            self.op_range(lo, hi)  # stage-boundary legality
            pos = hi
        if pos != self.n_layers:
            raise ValueError(
                f"{self.name}: route covers [0,{pos}) but the model has {self.n_layers} layers"
            )

    def run_route(self, x, spans):
        """Execute an arbitrary (validated) multi-segment route eagerly —
        the per-model reference the multi-cut equivalence tests pin
        against ``run_all``."""
        self.check_route(spans)
        state = self.init_state(x)
        for lo, hi in spans:
            state = self.run_segment(state, lo, hi)
        return self.finalize(state)

    def run_all(self, x):
        return self.finalize(self.run_segment(self.init_state(x), 0, self.n_layers))


def stage_ops_from_graph(
    graph: LayerGraph, impl: str = "xla"
) -> tuple[list[tuple[str, Callable]], list[tuple[int, int]]]:
    """Fine-grained (op, span) lists from a coarse graph whose metas carry
    ``attrs["stages"]`` callables — one executable op per stage, spanning
    that stage's primitive layers in the *expanded* graph. ``impl`` picks
    a registered stage-callable variant where one exists."""
    from ..models.yolov8 import node_stages

    ops, spans, pos = [], [], 0
    for l in graph:
        if not l.attrs.get("stages"):
            raise ValueError(f"{l.name}: no stage callables; cannot stage at fine granularity")
        for sname, nprims, fn in node_stages(l, impl):
            ops.append((sname, fn))
            spans.append((pos, pos + nprims))
            pos += nprims
    return ops, spans


def fuse_groups_of(graph: LayerGraph) -> list[tuple[int, int]]:
    """Layer-index spans of the graph's marked fused blocks
    (``attrs["fuse"]`` on the lead layer — see the model layer_graphs)."""
    return [
        (i, i + l.attrs["fuse"]["span"]) for i, l in enumerate(graph) if "fuse" in l.attrs
    ]


def pix2pix_staged(cfg, params, batch_dtype=None, granularity: str = "coarse") -> StagedModel:
    from ..models.pix2pix import Pix2PixGenerator, generator_ops

    gen = Pix2PixGenerator(cfg)
    graph = gen.layer_graph()
    groups = fuse_groups_of(graph)  # ops align 1:1 with (primitive) layers
    if granularity == "fine":
        # the pix graph is already primitive-only; the expanded view keeps
        # the coarse index map so plans annotate coarse spans uniformly
        graph = graph.expand()
    return StagedModel(
        name=f"pix2pix[{cfg.deconv_mode}]",
        ops=generator_ops(cfg),
        params=params["generator"] if "generator" in params else params,
        graph=graph,
        init_state=lambda x: {"x": x.astype(cfg.act_dtype), "skips": []},
        finalize=lambda s: s["x"],
        batch_independent=cfg.batch_independent,
        variant_ops={"pallas_fused": generator_ops(cfg, impl="pallas_fused")},
        variant_groups=groups,
    )


def yolo_staged(cfg, params, granularity: str = "coarse") -> StagedModel:
    """YOLO staged model at ``coarse`` (one op per composite node) or
    ``fine`` granularity (expanded primitive graph, one op per sub-block
    stage callable — cuts inside ``c2f``/``sppf``/``head`` become
    executable)."""
    from ..models.yolov8 import YOLOv8

    if granularity not in ("coarse", "fine"):
        raise ValueError(f"granularity must be 'coarse' or 'fine', got {granularity!r}")
    m = YOLOv8(cfg)
    coarse = m.layer_graph()
    if granularity == "fine":
        ops, spans = stage_ops_from_graph(coarse)
        vops, _ = stage_ops_from_graph(coarse, impl="pallas_fused")
        graph, op_spans = coarse.expand(), spans
        # fused blocks whose variant spans multiple stage ops (the SPPF
        # pool pyramid: three pool stages -> one kernel) must switch impl
        # atomically; every other op switches individually, as before —
        # ConvBlock fuse groups live inside a single stage callable
        multi = []
        for glo, ghi in fuse_groups_of(graph):
            a = max(i for i, (lo, _hi) in enumerate(spans) if lo <= glo)
            b = min(i + 1 for i, (_lo, hi) in enumerate(spans) if hi >= ghi)
            if b - a > 1:
                multi.append((a, b))
        covered = {i for a, b in multi for i in range(a, b)}
        groups = sorted(multi + [(i, i + 1) for i in range(len(ops)) if i not in covered])
    else:
        ops, graph, op_spans = m.staged_ops(coarse), coarse, None
        vops = m.staged_ops(coarse, impl="pallas_fused")
        # every op is stage-atomic (a coarse node's fused blocks live
        # wholly inside its one stage callable), so groups are single ops
        groups = [(i, i + 1) for i in range(len(ops))]
    return StagedModel(
        name=cfg.name,
        ops=ops,
        params=params,
        graph=graph,
        init_state=lambda x: {"x": x.astype(cfg.act_dtype)},
        finalize=lambda s: {"p3": s["o3"], "p4": s["o4"], "p5": s["o5"]},
        op_spans=op_spans,
        variant_ops={"pallas_fused": vops},
        variant_groups=groups,
    )


@dataclasses.dataclass
class TickLog:
    tick: int
    engine: str
    work: str


class TwoModelPipeline:
    """Steady-state double-buffered execution of a HaX-CoNN schedule.

    Thin wrapper over the generic ``serve.StreamExecutor``: the two-model
    swap schedule is expressed as two counter-phased routes (A: con then
    flex, B: flex then con) with one stream per model, which the executor
    runs tick-for-tick as the original phase-1/phase-2 loop did.
    """

    def __init__(
        self,
        model_a: StagedModel,
        model_b: StagedModel,
        plan,
        place_con: Callable | None = None,
        place_flex: Callable | None = None,
    ):
        self.a, self.b = model_a, model_b
        # accept the unified entry point's PlanIR or a legacy HaxConnResult
        ir = plan if isinstance(plan, PlanIR) else plan.ir
        self.pa, self.pb = ir.partitions
        self.plan = ir
        self.place_con = place_con or (lambda x: x)
        self.place_flex = place_flex or (lambda x: x)
        self.log: list[TickLog] = []

    def run_stream(self, frames_a, frames_b):
        """frames_*: lists of model inputs (equal length). Returns
        (outputs_a, outputs_b) in input order + populates ``self.log``."""
        from ..serve.executor import StreamExecutor  # lazy: serve imports this module
        from ..serve.streams import StreamSpec
        from .plan_ir import make_plan_ir

        assert len(frames_a) == len(frames_b)
        la, lb = self.a.n_layers, self.b.n_layers
        # the scheduler's typed IR drives the executor; rebuild it from the
        # (possibly caller-overridden) partition points
        ir = self.plan
        if ir is None or ir.partitions != [self.pa, self.pb]:
            ir = make_plan_ir(
                (self.a.name, self.b.name),
                ("con", "flex"),
                [[(0, 0, self.pa), (1, self.pa, la)], [(1, 0, self.pb), (0, self.pb, lb)]],
                kind="haxconn",
            )
        ex = StreamExecutor(
            [self.a, self.b],
            ir,
            [StreamSpec("A", 0), StreamSpec("B", 1)],
            max_queue=max(1, len(frames_a)),
            place_fns=[self.place_con, self.place_flex],
            engine_names=["con", "flex"],
            model_labels=["A", "B"],
            # the two-model pipeline is the paper-faithful correctness
            # harness: keep the eager op sequence (bit-exact vs run_all)
            jit_segments=False,
        )
        for fa, fb in zip(frames_a, frames_b):
            ok = ex.submit(0, fa) and ex.submit(1, fb)
            if not ok:
                raise RuntimeError("pipeline frame queue refused a frame (depth mis-sized)")
        outs = ex.run_until_drained()
        self.log = ex.log
        return outs["A"], outs["B"]


def submesh_placers(mesh_devices, n_con: int):
    """Split a flat device list into (constrained, flexible) placement fns."""
    con, flex = list(mesh_devices[:n_con]), list(mesh_devices[n_con:])

    def place(devs):
        def f(state):
            return jax.tree.map(lambda x: jax.device_put(x, devs[0]), state)

        return f

    return place(con or flex), place(flex or con)
