"""Compute-engine abstraction.

The paper schedules across the Jetson's GPU and DLA. On a TPU pod the
same role is played by *disjoint submeshes* with different sizes and (to
model the DLA's restricted op set) different capability constraints. The
cost model and the HaX-CoNN scheduler consume only this abstraction, so
the identical machinery drives:

  * the faithful Jetson reproduction (calibrated GPU/DLA engine specs),
  * TPU submesh co-serving (two models sharing one pod),
  * and prefill/decode-style disaggregation.
"""
from __future__ import annotations

import dataclasses
from typing import Any

# ---- hardware constants -------------------------------------------------------
# TPU v5e (target hardware for the framework):
TPU_V5E_BF16_FLOPS = 197e12  # per chip
TPU_V5E_HBM_BW = 819e9  # bytes/s per chip
TPU_V5E_ICI_BW = 50e9  # bytes/s per link (~4 links/chip on a 2D torus)

# Jetson AGX Orin engine efficiencies, calibrated so that the cost model
# lands on the paper's measured standalone throughputs (Table IV context:
# Pix2Pix G is ~12.1 GFLOP/frame at 256x256; GPU ~172 FPS, balanced DLA
# ~148 FPS). These are *effective* (achieved) rates, not peaks.
JETSON_ORIN_GPU_FLOPS = 2.1e12
JETSON_ORIN_GPU_BW = 204.8e9
JETSON_ORIN_DLA_FLOPS = 1.85e12
JETSON_ORIN_DLA_BW = 102.4e9
JETSON_XFER_BW = 32e9  # engine<->engine via shared DRAM


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    name: str
    n_chips: int
    peak_flops: float  # total achievable FLOP/s for the engine
    hbm_bw: float  # total bytes/s
    link_bw: float  # bytes/s to the peer engine
    constraints: tuple[Any, ...] = ()
    efficiency: float = 1.0  # multiplier on peak_flops (achievable utilization)
    # Optional concrete ``jax.Device`` this spec executes on. Excluded from
    # eq/hash: binding is a *placement* decision, so a bound slice plans
    # identically to the abstract specs it was derived from.
    device: Any = dataclasses.field(default=None, compare=False)

    @property
    def flops(self):
        return self.peak_flops * self.efficiency

    def bound(self, device) -> "EngineSpec":
        """This spec bound to a concrete ``jax.Device`` placement target."""
        return dataclasses.replace(self, device=device)

    def supports(self, layer) -> list:
        """Return the list of violated constraints for a layer (empty = legal).

        Composite metas (hierarchical graphs) are checked through their
        primitive decomposition too: a ``c2f`` block containing one
        illegal primitive is illegal as a whole at coarse granularity —
        the planner must expand it to route around the primitive.

        The result is memoized per (layer object, engine): the multi-cut
        planner calls this on every layer of every candidate span, and
        walking a composite's decomposition each time dominated planning
        profiles. Keying on the object identity is sound because graph
        rewrites (surgery, expansion) ``clone()`` metas rather than
        mutating them in place; the cached entry pins the layer so a
        recycled ``id`` can never alias a dead one. Callers must treat
        the returned list as read-only."""
        cache = self.__dict__.get("_supports_cache")
        if cache is None:
            cache = {}
            # frozen dataclass: the cache is identity-keyed scratch state,
            # not part of the spec's value (hash/eq are unaffected)
            object.__setattr__(self, "_supports_cache", cache)
        hit = cache.get(id(layer))
        if hit is not None and hit[0] is layer:
            return hit[1]
        out = []
        for c in self.constraints:
            v = c.check(layer)
            if v is not None:
                out.append(v)
        for sub in getattr(layer, "sublayers", None) or ():
            out.extend(self.supports(sub))
        cache[id(layer)] = (layer, out)
        return out


def jetson_orin_engines(constraints_dla=(), constraints_gpu=()):
    gpu = EngineSpec(
        "GPU", 1, JETSON_ORIN_GPU_FLOPS, JETSON_ORIN_GPU_BW, JETSON_XFER_BW, tuple(constraints_gpu)
    )
    dla = EngineSpec(
        "DLA", 1, JETSON_ORIN_DLA_FLOPS, JETSON_ORIN_DLA_BW, JETSON_XFER_BW, tuple(constraints_dla)
    )
    return gpu, dla


def tpu_submesh_engines(
    n_big: int = 192,
    n_small: int = 64,
    constraints_small=(),
    efficiency: float = 0.6,
):
    """Split one 256-chip pod into a flexible 'GPU-analogue' submesh and a
    constrained 'DLA-analogue' submesh for concurrent multi-model serving."""
    big = EngineSpec(
        "TPU-BIG",
        n_big,
        n_big * TPU_V5E_BF16_FLOPS,
        n_big * TPU_V5E_HBM_BW,
        TPU_V5E_ICI_BW * min(n_big, n_small),
        (),
        efficiency,
    )
    small = EngineSpec(
        "TPU-SMALL",
        n_small,
        n_small * TPU_V5E_BF16_FLOPS,
        n_small * TPU_V5E_HBM_BW,
        TPU_V5E_ICI_BW * min(n_big, n_small),
        tuple(constraints_small),
        efficiency,
    )
    return big, small


class DevicePool:
    """Discovered ``jax.Device``s sliced into per-replica engine groups.

    The fleet (``repro.serve.fleet``) replicates the planned pipeline R
    times; each replica gets a slice of the pool and an engine tuple
    bound to that slice. On multi-device hosts the slices are disjoint
    (``D // R`` devices each, round-robin reuse once R exceeds D); on
    1-device hosts — CPU CI — every replica binds the virtual 2-engine
    GPU/DLA pair to the single device, so the whole fleet still runs.
    Placement is exposed in the shapes ``StreamExecutor`` consumes: each
    engine's device, where stage-in uploads frames (``engine_devices``),
    per-engine ``place_fns`` (``jax.device_put`` closures where a route can
    cross chips, the identity where it cannot), and the weights as
    per-engine copies made once at build time (``place_params``); on a
    1-device pool all three collapse to identity so the hot path pays
    nothing.
    """

    def __init__(self, engines, devices=None):
        if devices is None:
            import jax

            devices = list(jax.devices())
        if not devices:
            raise ValueError("DevicePool needs at least one device")
        self.devices = list(devices)
        self.engines = tuple(engines)
        if not self.engines:
            raise ValueError("DevicePool needs at least one engine spec")

    @classmethod
    def discover(cls, engines=None, constraints_dla=(), constraints_gpu=()):
        """Pool over ``jax.devices()``; defaults to the Jetson-analogue
        (DLA, GPU) virtual pair in planning order when no specs are given."""
        if engines is None:
            gpu, dla = jetson_orin_engines(constraints_dla, constraints_gpu)
            engines = (dla, gpu)
        return cls(engines)

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def replica_devices(self, replica: int, n_replicas: int) -> list:
        """The device slice backing one replica (wraps when R > D)."""
        if replica < 0 or replica >= n_replicas:
            raise ValueError(f"replica {replica} out of range for {n_replicas}")
        per = max(1, len(self.devices) // max(1, n_replicas))
        return [self.devices[(replica * per + j) % len(self.devices)] for j in range(per)]

    def worker_pool(self, worker: int, n_workers: int) -> "DevicePool":
        """A sub-pool over one worker *process*'s device slice.

        The multi-process fleet (``repro.serve.multiproc``) spawns R
        workers; each builds its replica group over the devices visible
        to *its* process. Slicing reuses the replica round-robin (wraps
        when R exceeds D), so a worker's pool is just this pool narrowed
        to its share — on 1-device hosts every worker sees the single
        device and placement stays identity."""
        return DevicePool(self.engines, devices=self.replica_devices(worker, n_workers))

    def engine_slice(self, replica: int, n_replicas: int) -> tuple[EngineSpec, ...]:
        """The pool's engine specs bound to this replica's devices."""
        devs = self.replica_devices(replica, n_replicas)
        return tuple(e.bound(devs[i % len(devs)]) for i, e in enumerate(self.engines))

    def engine_devices(self, replica: int, n_replicas: int) -> list | None:
        """Each engine's device in this replica's slice, where stage-in
        uploads the frames of routes that start there; None on a 1-device
        pool, where there is one place to upload to."""
        if len(self.devices) == 1:
            return None
        return [e.device for e in self.engine_slice(replica, n_replicas)]

    def place_fns(self, replica: int, n_replicas: int) -> list:
        """Per-engine state-placement closures for ``StreamExecutor``: a
        ``jax.device_put`` onto the engine's device where a route can cross
        chips, the identity where it cannot. A state lives on the chip
        stage-in uploaded it to or a segment left it on, so on a 1-device
        pool, and for a replica whose engines all bind one chip, every
        closure is the identity."""
        devs = self.engine_devices(replica, n_replicas)
        if devs is None or len(set(devs)) == 1:
            return [lambda state: state for _ in self.engines]
        import jax

        return [
            lambda state, dev=dev: jax.tree.map(lambda x: jax.device_put(x, dev), state)
            for dev in devs
        ]

    def place_params(self, models, replica: int, n_replicas: int) -> list | None:
        """Every model's params, copied once onto each engine's device of
        this replica: ``out[engine][model]``. Engines that share a device
        share the copy. None on a 1-device pool, where the models' own
        params already live on the one device."""
        if len(self.devices) == 1:
            return None
        import jax

        on_device: dict = {}
        out = []
        for e in self.engine_slice(replica, n_replicas):
            if e.device not in on_device:
                on_device[e.device] = [jax.device_put(m.params, e.device) for m in models]
            out.append(on_device[e.device])
        return out
