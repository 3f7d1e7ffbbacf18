"""``build_server`` — the one-call serving facade.

The pieces of the serving stack (staged models, the unified
``repro.core.plan`` scheduler, stream specs with SLO policies, admission
control, open-loop traffic, and the online re-planner) compose freely,
but every driver was re-assembling them by hand. ``build_server`` builds
the whole stack for the repo's reference workload (Pix2Pix
reconstruction + YOLOv8 detection on the calibrated Jetson engine pair)
and returns a ``ServerBundle`` holding each layer, so CLIs, examples,
benchmarks, and tests drive one construction path:

    bundle = build_server(n_pix=4, n_yolo=1, deadline_ms=50.0,
                          traffic=TrafficConfig(process="poisson", rate_hz=30),
                          admission=True)
    report = bundle.run_open_loop(horizon_s=2.0)

Unlike ``build_pix_yolo_serving`` (kept for ``NModelPlan`` callers), the
facade plans through ``repro.core.plan`` and carries the ``PlanIR``
contract end-to-end — including ``max_cuts="auto"`` budget escalation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from ..core.api import plan as core_plan
from ..core.cost_model import CostProvider, OnlineCost, make_cost_provider
from ..core.engine import DevicePool
from ..core.plan_ir import PlanIR
from .admission import AdmissionConfig
from .batching import BatchConfig
from .demo import _build_pix_yolo_models, merge_flags_for
from .fleet import FleetServer
from .multiproc import ProcFleetServer
from .replanner import ReplanConfig, Replanner
from .server import MultiStreamServer
from .streams import StreamSpec
from .traffic import SLOPolicy, TrafficConfig, run_open_loop


@dataclasses.dataclass
class ServerBundle:
    """Every layer of one constructed serving stack, plus drivers.

    ``traffic`` maps stream name -> ``TrafficConfig`` (empty when built
    without open-loop traffic); ``replanner``/``admission`` are None when
    those layers are off."""

    models: list
    plan: PlanIR
    streams: list[StreamSpec]
    engines: tuple  # planning order: (dla, gpu)
    provider: CostProvider
    server: MultiStreamServer | FleetServer | ProcFleetServer
    replanner: Replanner | None
    admission: AdmissionConfig | None
    traffic: dict[str, TrafficConfig]
    img: int = 64
    replicas: int = 1
    workers: int = 0

    def frame_for(self, stream_name: str, t: int = 0):
        """A deterministic input frame for the named stream (seeded by
        stream identity + frame index) — the default open-loop source."""
        si = next(i for i, s in enumerate(self.streams) if s.name == stream_name)
        return jax.random.normal(jax.random.key(1000 * si + t), (1, self.img, self.img, 3))

    def run_open_loop(
        self,
        horizon_s: float,
        frame_fn: Callable[[str], Any] | None = None,
        drain: bool = True,
        max_wall_s: float | None = None,
    ) -> dict:
        """Drive the server with the bundle's traffic processes for
        ``horizon_s`` seconds of arrival time; returns ``server.report()``."""
        if not self.traffic:
            raise ValueError("bundle was built without traffic; pass traffic= to build_server")
        if frame_fn is None:
            counts: dict[str, int] = {}

            def frame_fn(name: str):
                t = counts.get(name, 0)
                counts[name] = t + 1
                return self.frame_for(name, t)

        return run_open_loop(
            self.server, self.traffic, frame_fn, horizon_s, drain=drain, max_wall_s=max_wall_s
        )

    def report(self) -> dict:
        return self.server.report()

    def close(self):
        """Release server resources — shuts down the worker processes of a
        multi-process fleet; a no-op for in-process servers."""
        close = getattr(self.server, "close", None)
        if close is not None:
            close()

    def __enter__(self) -> "ServerBundle":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _normalize_slos(slos, deadline_ms, streams: list[StreamSpec]):
    """Resolve the facade's SLO inputs to one policy (or None) per stream.

    ``slos`` may be a single ``SLOPolicy`` (every stream), a dict keyed by
    stream name or model index, or None. ``deadline_ms`` is the shorthand:
    one deadline for all streams, detection streams (model 1) at tier 0
    and reconstruction streams at tier 1 — the paper's priority split."""
    if slos is None and deadline_ms is None:
        return [None] * len(streams)
    out = []
    for s in streams:
        if isinstance(slos, SLOPolicy):
            out.append(slos)
        elif isinstance(slos, dict):
            p = slos.get(s.name, slos.get(s.model_index))
            out.append(p)
        else:
            tier = 0 if s.model_index == 1 else 1
            out.append(SLOPolicy(deadline_ms=deadline_ms, tier=tier, name=f"{s.name}-slo"))
    return out


def _normalize_traffic(traffic, streams: list[StreamSpec]) -> dict[str, TrafficConfig]:
    """One ``TrafficConfig`` per stream: a single config fans out to every
    stream (re-seeded per stream so arrival processes are independent);
    a dict keyed by stream name passes through (missing names get no
    traffic)."""
    if traffic is None:
        return {}
    if isinstance(traffic, TrafficConfig):
        return {
            s.name: dataclasses.replace(traffic, seed=traffic.seed + si)
            for si, s in enumerate(streams)
        }
    unknown = set(traffic) - {s.name for s in streams}
    if unknown:
        raise ValueError(f"traffic for unknown streams: {sorted(unknown)}")
    return dict(traffic)


def build_server(
    *,
    # workload
    img: int = 64,
    base: int = 8,
    n_pix: int = 4,
    n_yolo: int = 1,
    seed: int = 0,
    norm: str = "batch",
    # planning (repro.core.plan)
    cost: str | CostProvider = "analytic",
    search: str = "auto",
    granularity: str = "coarse",
    stride: int = 1,
    max_cuts: int | str = 1,
    impl: str = "xla",
    # serving
    max_queue: int = 4,
    microbatch: int = 1,
    merge_batches: bool | list[bool] | None = None,
    batching: BatchConfig | int | None = None,
    dispatch: str = "overlapped",
    jit_segments: bool = True,
    # SLOs + open loop
    slos: SLOPolicy | dict | None = None,
    deadline_ms: float | None = None,
    traffic: TrafficConfig | dict[str, TrafficConfig] | None = None,
    admission: AdmissionConfig | bool | None = None,
    resolution_flexible: bool | list[bool] = False,
    # online re-planning
    replan: bool | ReplanConfig = False,
    # fleet replication
    replicas: int = 1,
    router_seed: int = 0,
    # multi-process fleet
    workers: int = 0,
    calibration_path: str | None = None,
    calib_sync_every: int = 16,
) -> ServerBundle:
    """Build the full serving stack in one call; see module docstring.

    ``merge_batches=None`` derives the per-model flags from batch
    independence (``merge_flags_for``). ``batching`` turns on the
    deadline-aware continuous-batching coalescer: pass a ``BatchConfig``
    or an int shorthand (``batching=8`` == ``BatchConfig(max_batch=8)``);
    it only engages on batch-independent models (``merge_batches``), so
    with the default ``norm="batch"`` pix2pix streams do not coalesce —
    use ``norm="instance"`` for the batched reconstruction workload.
    ``admission=True`` uses the default degradation ladder;
    ``replan=True`` the default ``ReplanConfig``. ``deadline_ms`` is the SLO shorthand (detection
    tier 0, reconstruction tier 1); pass ``slos`` for full control.
    ``impl`` selects the implementation-planning mode (``xla`` | ``auto``
    | ``pallas``); segments planned ``pallas_fused`` stage the fused
    serving kernels end-to-end.

    ``replicas > 1`` returns the bundle over a ``FleetServer``: R
    replicated (plan, executor) groups over a ``DevicePool`` behind a
    sticky load-aware ``FleetRouter``. The plan is solved once — over
    replica 0's engine slice, which is value-identical to every other
    slice (only the device binding differs) — and each replica gets its
    own ``Replanner``, all sharing one thread-safe ``OnlineCost`` so
    calibration is fleet-wide.

    ``workers > 0`` returns the bundle over a ``ProcFleetServer`` instead:
    R worker *processes*, each rebuilding the same replica group from the
    serialized plan, behind the same sticky router over IPC
    (``serve.multiproc``). Mutually exclusive with ``replicas > 1`` — one
    replica group per worker process. ``cost`` must then be a provider
    name (the build spec crosses the process boundary as JSON), and with
    ``replan`` on the workers' calibrations sync fleet-wide every
    ``calib_sync_every`` front ticks, checkpointing atomically to
    ``calibration_path`` (which also warm-starts workers on spawn). Call
    ``bundle.close()`` (or use the bundle as a context manager) to shut
    the workers down. On a TPU backend ``workers > 0`` raises before
    anything is spawned: this process already holds the chip, and a chip
    belongs to one process."""
    if workers and jax.default_backend() == "tpu":
        raise RuntimeError(
            "build_server(workers>0) needs one process per accelerator, but this "
            "process already holds the TPU; use replicas=R to serve R replicas "
            "from this process"
        )
    if workers and replicas > 1:
        raise ValueError(
            "workers and replicas are mutually exclusive: a multi-process fleet "
            "hosts one replica group per worker process"
        )
    if workers and not isinstance(cost, str):
        raise ValueError(
            "multi-process fleet needs a cost provider *name* (the build spec "
            f"crosses the process boundary as JSON), got {type(cost).__name__}"
        )
    provider = cost if isinstance(cost, CostProvider) else make_cost_provider(cost)
    models, streams, (gpu, dla) = _build_pix_yolo_models(
        img=img, base=base, n_pix=n_pix, n_yolo=n_yolo, seed=seed, norm=norm,
        granularity=granularity,
    )
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    pool = DevicePool((dla, gpu))
    # one plan serves every replica: slice 0's bound engines plan exactly
    # like the abstract pair (device binding is excluded from spec equality)
    plan_engines = list(pool.engine_slice(0, replicas)) if replicas > 1 else [dla, gpu]
    plan_ir = core_plan(
        [m.graph for m in models],
        plan_engines,
        search=search,
        stride=stride,
        max_cuts=max_cuts,
        cost=provider,
        impl=impl,
    )
    policies = _normalize_slos(slos, deadline_ms, streams)
    streams = [
        dataclasses.replace(s, slo=p) if p is not None else s
        for s, p in zip(streams, policies)
    ]
    if merge_batches is None:
        merge_batches = merge_flags_for(models)
    if isinstance(batching, int):
        batching = BatchConfig(max_batch=batching)
    if admission is True:
        admission = AdmissionConfig()
    elif admission is False:
        admission = None
    replanner = None
    replanners = None
    if replan and not workers:
        config = replan if isinstance(replan, ReplanConfig) else None
        if replicas > 1:
            # one shared OnlineCost: every replica's Replanner reuses the
            # instance (thread-safe drain), so all replicas' segment
            # observations feed a single fleet-wide calibration store
            shared = provider if isinstance(provider, OnlineCost) else OnlineCost(base=provider)
            replanners = [
                Replanner(
                    [m.graph for m in models], [dla, gpu], config=config, base_provider=shared
                )
                for _ in range(replicas)
            ]
            replanner = replanners[0]
        else:
            replanner = Replanner(
                [m.graph for m in models], [dla, gpu], config=config, base_provider=provider
            )
    if workers:
        # workers rebuild their replanners in-process; the front only
        # carries the serialized config (True -> worker-side default)
        replan_payload = None
        if replan:
            replan_payload = (
                dataclasses.asdict(replan) if isinstance(replan, ReplanConfig) else {}
            )
        server = ProcFleetServer(
            plan_ir,
            streams,
            workers=workers,
            build={
                "img": img, "base": base, "n_pix": n_pix, "n_yolo": n_yolo,
                "seed": seed, "norm": norm, "granularity": granularity,
            },
            router_seed=router_seed,
            max_queue=max_queue,
            microbatch=microbatch,
            merge_batches=merge_batches,
            batching=batching,
            dispatch=dispatch,
            jit_segments=jit_segments,
            admission=admission,
            resolution_flexible=resolution_flexible,
            cost=cost,
            replan=replan_payload,
            calibration_path=calibration_path,
            calib_sync_every=calib_sync_every,
        )
    elif replicas > 1:
        server = FleetServer(
            models,
            plan_ir,
            streams,
            replicas=replicas,
            pool=pool,
            router_seed=router_seed,
            max_queue=max_queue,
            microbatch=microbatch,
            merge_batches=merge_batches,
            batching=batching,
            dispatch=dispatch,
            jit_segments=jit_segments,
            replanners=replanners,
            admission=admission,
            resolution_flexible=resolution_flexible,
        )
    else:
        server = MultiStreamServer(
            models,
            plan_ir,
            streams,
            max_queue=max_queue,
            microbatch=microbatch,
            merge_batches=merge_batches,
            batching=batching,
            dispatch=dispatch,
            jit_segments=jit_segments,
            replanner=replanner,
            admission=admission,
            resolution_flexible=resolution_flexible,
        )
    return ServerBundle(
        models=models,
        plan=plan_ir,
        streams=streams,
        engines=(dla, gpu),
        provider=provider,
        server=server,
        replanner=replanner,
        admission=admission,
        traffic=_normalize_traffic(traffic, streams),
        img=img,
        replicas=replicas,
        workers=workers,
    )
