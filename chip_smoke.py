#!/usr/bin/env python3
"""Chip smoke test: the Pix2Pix + YOLOv8 serving path on a TPU at published size.

    python chip_smoke.py              # one chip: phases (a) xla, (b) pallas, (c) open loop
    python chip_smoke.py --chips 4    # four chips: replica fleets against one replica
    python chip_smoke.py --rehearse   # CPU at smoke size: control flow only, prints no result

The stack is built the way users build it, ``serve.build_server(img=256,
base=64, n_pix=4, n_yolo=1)``: ``configs/pix2pix_mri.CONFIG_CROPPING``
(54.4 M generator parameters) beside ``configs/yolov8_stroke.CONFIG``
(YOLOv8n, width 0.25, depth 0.33), with weights drawn from a seed and
seeded CT phantoms (``data/synthetic.py``) as frames.

Every served output is checked against a plain float32 reference built
from the same params (``Pix2PixGenerator(cfg)(params, x)`` and the YOLOv8
forward) under ``jax.default_matmul_precision("highest")``. The served
path runs at the TPU's default matmul precision, which rounds conv
operands to bfloat16 (relative 2**-9) and accumulates in float32. A CPU
emulation of exactly that rounding, on these seeded weights and phantoms,
predicts a Pix2Pix max abs error of 0.019 (tanh output in [-1, 1]) and a
YOLOv8 relative L2 error of 0.12 (max abs / max |ref| 0.13): its ~60
batch-normed convs re-normalise 8x8 maps with per-channel statistics,
which amplifies the rounding. The tolerances below are three times those
predictions, far below the error of any broken output (1.0 for zeros).

Each phase prints one JSON line; any failed check raises, and the script
exits non-zero. The last line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits non-zero with a one-line reason.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

PIX_MAX_ABS = 0.06  # 3x the predicted 0.019 on a tanh output
DET_REL_L2 = 0.36  # 3x the predicted 0.12
DET_MAX_REL = 0.4  # 3x the predicted 0.13
POOL = 16  # distinct phantoms; frame (stream si, index t) is pool[(7 si + t) % POOL]
FRAMES = 3  # closed-loop frames per stream
HORIZON_S = 3.0  # open-loop seconds of arrivals
RATE_HZ = 10.0  # open-loop arrivals per stream, well under one chip's capacity


def _args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true", help="CPU, smoke size; prints no result")
    return ap.parse_args()


def _emit(**rec):
    print(json.dumps(rec), flush=True)


class Smoke:
    def __init__(self, args):
        import jax
        import numpy as np

        from repro.configs import pix2pix_mri, yolov8_stroke
        from repro.data.synthetic import PhantomConfig, make_phantom_pair
        from repro.models import Pix2PixGenerator, YOLOv8

        self.args = args
        self.img, self.base = (32, 8) if args.rehearse else (256, 64)
        self.device = jax.devices()[0]
        pcfg = dataclasses.replace(pix2pix_mri.CONFIG_CROPPING, img_size=self.img, base=self.base)
        ycfg = dataclasses.replace(yolov8_stroke.CONFIG, img_size=self.img)
        self.ref_fns = [jax.jit(Pix2PixGenerator(pcfg).__call__), jax.jit(YOLOv8(ycfg).__call__)]
        rng = np.random.default_rng(0)
        cfg = PhantomConfig(img_size=self.img)
        # host copies: served frames are donated on the chip, so each offer
        # gets a fresh device array and the reference reads the host copy
        self.pool = [
            np.repeat(make_phantom_pair(rng, cfg)[0], 3, axis=-1)[None] for _ in range(POOL)
        ]

    # -- building blocks --------------------------------------------------

    def build(self, **kw):
        from repro.serve import build_server

        return build_server(img=self.img, base=self.base, n_pix=4, n_yolo=1, seed=0, **kw)

    @staticmethod
    def frame_index(si: int, t: int) -> int:
        return (7 * si + t) % POOL

    def reference(self, bundle):
        """Memoised float32 reference of each model on each pool frame."""
        import jax
        import numpy as np

        memo = {}

        def ref(mi: int, k: int):
            if (mi, k) not in memo:
                with jax.default_matmul_precision("highest"):
                    out = self.ref_fns[mi](bundle.models[mi].params, self.pool[k])
                memo[mi, k] = jax.tree.map(np.asarray, out)
            return memo[mi, k]

        return ref

    @staticmethod
    def errors(pairs) -> dict:
        """Max abs, max abs over max |ref|, and relative L2 over (served,
        reference) pytree pairs; raises on a non-finite or misshapen output."""
        import jax
        import numpy as np

        max_abs = ref_max = sq = ref_sq = 0.0
        for got, want in pairs:
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
                g = np.asarray(g, np.float64)
                if g.shape != w.shape:
                    raise AssertionError(f"served shape {g.shape} != reference {w.shape}")
                if not np.isfinite(g).all():
                    raise AssertionError("served output is not finite")
                d = g - w
                max_abs = max(max_abs, float(np.abs(d).max()))
                ref_max = max(ref_max, float(np.abs(w).max()))
                sq += float((d * d).sum())
                ref_sq += float((w.astype(np.float64) ** 2).sum())
        return {
            "max_abs": max_abs,
            "max_rel": max_abs / ref_max,
            "rel_l2": (sq / ref_sq) ** 0.5,
            "n": len(pairs),
        }

    @staticmethod
    def check(errs: list[dict]):
        pix, det = errs
        if pix["max_abs"] > PIX_MAX_ABS:
            raise AssertionError(f"pix2pix max abs error {pix['max_abs']} > {PIX_MAX_ABS}")
        if det["rel_l2"] > DET_REL_L2 or det["max_rel"] > DET_MAX_REL:
            raise AssertionError(f"yolov8 error {det} over ({DET_REL_L2}, {DET_MAX_REL})")

    def peak_bytes(self):
        stats = self.device.memory_stats()
        return stats.get("peak_bytes_in_use") if stats else None

    def run_phase(self, name: str, fn):
        from repro.launch import compile_cache

        before, t0 = compile_cache.stats(), time.perf_counter()
        rec = fn()
        after = compile_cache.stats()
        _emit(
            phase=name,
            wall_s=time.perf_counter() - t0,
            compile_s=after["compile_s"] - before["compile_s"],
            cache_hits=after["hits"] - before["hits"],
            cache_misses=after["misses"] - before["misses"],
            peak_bytes_in_use=self.peak_bytes(),
            **rec,
        )

    # -- phases -------------------------------------------------------------

    def kernels_in_plan(self, bundle) -> dict:
        """Lower every ``pallas_fused`` segment and count its Mosaic custom
        calls: on the chip each must hold compiled kernels, none interpreted."""
        import jax

        n_seg = n_kernels = 0
        for mi, model in enumerate(bundle.models):
            state = jax.eval_shape(
                model.init_state, jax.ShapeDtypeStruct((1, self.img, self.img, 3), "float32")
            )
            for seg in bundle.plan.route(mi):
                if seg.impl == "pallas_fused":
                    fn = model.jitted_segment_fn(seg.lo, seg.hi, impl=seg.impl)
                    calls = fn.lower(model.params, state).as_text().count("tpu_custom_call")
                    if not self.args.rehearse and calls == 0:
                        raise AssertionError(f"{model.name}[{seg.lo}:{seg.hi}) has no TPU kernel")
                    n_seg, n_kernels = n_seg + 1, n_kernels + calls
                state = jax.eval_shape(model.segment_fn(seg.lo, seg.hi, seg.impl), model.params, state)
        if n_seg == 0:
            raise AssertionError("impl='pallas' planned no pallas_fused segment")
        return {"pallas_segments": n_seg, "tpu_kernels": n_kernels}

    def closed_loop(self, impl: str) -> dict:
        import jax.numpy as jnp

        frames = FRAMES
        bundle = self.build(impl=impl, max_queue=frames)
        rec = self.kernels_in_plan(bundle) if impl == "pallas" else {}
        for t in range(frames):
            for si, s in enumerate(bundle.streams):
                x = jnp.asarray(self.pool[self.frame_index(si, t)])
                if bundle.server.offer(s.name, x) != "admit":
                    raise AssertionError(f"{s.name} refused closed-loop frame {t}")
        outs = bundle.server.drain()
        ref = self.reference(bundle)
        pairs = ([], [])
        for si, s in enumerate(bundle.streams):
            if len(outs[s.name]) != frames:
                raise AssertionError(f"{s.name} served {len(outs[s.name])} of {frames} frames")
            for t, got in enumerate(outs[s.name]):
                pairs[s.model_index].append((got, ref(s.model_index, self.frame_index(si, t))))
        errs = [self.errors(p) for p in pairs]
        self.check(errs)
        return {"impl": impl, "frames": sum(len(v) for v in outs.values()),
                "pix2pix": errs[0], "yolov8": errs[1], **rec}

    def open_loop(self) -> dict:
        import jax.numpy as jnp

        from repro.serve import TrafficConfig

        bundle = self.build(
            impl="xla",
            deadline_ms=100.0,
            traffic=TrafficConfig("poisson", rate_hz=RATE_HZ, seed=0),
            admission=True,
        )
        server = bundle.server
        # warm every segment executable, then measure a fresh window
        for si, s in enumerate(bundle.streams):
            server.offer(s.name, jnp.asarray(self.pool[self.frame_index(si, 0)]))
        server.drain()
        server.reset_metrics()
        warm = {s.name: len(server.executor.outputs[s.name]) for s in bundle.streams}
        rec = _Recorder(bundle, self)
        report = bundle.run_open_loop(
            HORIZON_S, frame_fn=rec.frame, max_wall_s=120.0 + 10 * HORIZON_S
        )
        adm = report["admission"]
        if report.get("worker_failures"):
            raise AssertionError(f"worker failures: {report['worker_failures']}")
        queued = adm["admitted"] + adm["shed_res"] + adm["shed_route"]
        if adm["admitted"] == 0 or report["frames"] != queued:
            raise AssertionError(f"admission ledger {adm} vs {report['frames']} completions")
        ref = self.reference(bundle)
        pairs = ([], [])
        si_of = {s.name: si for si, s in enumerate(bundle.streams)}
        for c in server.executor.completions:
            fid = c.frame_id - warm[c.stream]
            if fid < 0:
                continue
            mi = bundle.streams[si_of[c.stream]].model_index
            pairs[mi].append((c.output, ref(mi, rec.accepted[c.stream][fid])))
        errs = [self.errors(p) for p in pairs]
        self.check(errs)
        return {"impl": "xla", "frames": report["frames"], "admission": adm,
                "goodput_fps": report["goodput_fps"], "latency_p99_ms": report["latency_p99_ms"],
                "pix2pix": errs[0], "yolov8": errs[1]}

    def fleet(self, replicas: int) -> dict:
        """Serve seeded open-loop arrivals with ``replicas`` replicas; every
        arrival is queued (no admission, deep queues) so runs compare 1:1."""
        import jax

        from repro.serve import TrafficConfig

        bundle = self.build(
            impl="xla", replicas=replicas, max_queue=256,
            traffic=TrafficConfig("poisson", rate_hz=RATE_HZ, seed=0),
        )
        counts: dict[str, int] = {}
        si_of = {s.name: si for si, s in enumerate(bundle.streams)}

        def frame(name):
            t = counts.get(name, 0)
            counts[name] = t + 1
            return jax.numpy.asarray(self.pool[self.frame_index(si_of[name], t)])

        bundle.run_open_loop(HORIZON_S, frame_fn=frame, max_wall_s=600.0)
        outs = bundle.server.drain()
        placement = {}
        if replicas > 1:
            fleet = bundle.server
            for r, srv in enumerate(fleet.servers):
                devs = set(fleet.pool.replica_devices(r, replicas))
                ep = srv.executor.engine_params
                engine_devs = [
                    {d for leaf in jax.tree.leaves(ep[e]) for d in leaf.devices()}
                    for e in range(len(ep))
                ]
                if not all(d <= devs for d in engine_devs):
                    raise AssertionError(f"replica {r} params on {engine_devs}, not {devs}")
                if replicas == 2 and len(set().union(*engine_devs)) != 2:
                    raise AssertionError(f"replica {r} engines share a chip: {engine_devs}")
                for name, vals in srv.executor.outputs.items():
                    for v in vals:
                        for leaf in jax.tree.leaves(v):
                            if not leaf.devices() <= devs:
                                raise AssertionError(f"{name} output on {leaf.devices()}")
                placement[r] = sorted(d.id for d in devs)
        # engine changes along each route: at replicas=2 each is a hop between chips
        hops = {
            m.name: sum(a.engine != b.engine for a, b in zip(route, route[1:]))
            for m, route in ((m, bundle.plan.route(mi)) for mi, m in enumerate(bundle.models))
        }
        return {"outs": outs, "counts": counts, "placement": placement, "hops": hops,
                "bundle": bundle}


class _Recorder:
    """Open-loop frame source that remembers which pool frame each
    accepted arrival carried (dropped arrivals get no executor frame id)."""

    def __init__(self, bundle, smoke: Smoke):
        self.smoke = smoke
        self.si_of = {s.name: si for si, s in enumerate(bundle.streams)}
        self.counts: dict[str, int] = {}
        self.accepted: dict[str, list[int]] = {}
        self._last: dict[str, int] = {}
        server = bundle.server
        offer = server.offer

        def recording_offer(name, frame):
            decision = offer(name, frame)
            if decision != "drop":
                self.accepted.setdefault(name, []).append(self._last[name])
            return decision

        server.offer = recording_offer

    def frame(self, name):
        import jax.numpy as jnp

        t = self.counts.get(name, 0)
        self.counts[name] = t + 1
        k = self.smoke.frame_index(self.si_of[name], t)
        self._last[name] = k
        return jnp.asarray(self.smoke.pool[k])


def _single_chip(smoke: Smoke):
    smoke.run_phase("a_closed_loop_xla", lambda: smoke.closed_loop("xla"))
    smoke.run_phase("b_closed_loop_pallas", lambda: smoke.closed_loop("pallas"))
    smoke.run_phase("c_open_loop_admission", smoke.open_loop)


def _four_chips(smoke: Smoke):
    import jax
    import numpy as np

    runs = {}

    def run(r):
        res = smoke.fleet(r)
        runs[r] = res
        rec = {"replicas": r, "frames": sum(len(v) for v in res["outs"].values()),
               "placement": res["placement"], "engine_hops": res["hops"]}
        if r != 1:
            base = runs[1]
            if res["counts"] != base["counts"]:
                raise AssertionError(f"arrivals differ: {res['counts']} vs {base['counts']}")
            pairs = ([], [])
            for s in res["bundle"].streams:
                got, want = res["outs"][s.name], base["outs"][s.name]
                if len(got) != len(want):
                    raise AssertionError(f"{s.name}: {len(got)} outputs vs {len(want)}")
                pairs[s.model_index].extend(
                    (g, jax.tree.map(np.asarray, w)) for g, w in zip(got, want)
                )
            errs = [Smoke.errors(p) for p in pairs]
            Smoke.check(errs)
            rec.update(vs_one_replica={"pix2pix": errs[0], "yolov8": errs[1]})
        return rec

    for r in (1, 4, 2):
        smoke.run_phase(f"fleet_replicas_{r}", lambda r=r: run(r))


def main() -> int:
    args = _args()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            print(f"chip_smoke: --rehearse runs on the CPU, JAX found {platform}", file=sys.stderr)
            return 2
    elif platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    _emit(device_kind=devices[0].device_kind, devices=len(devices), compile_cache=cache_dir,
          jax=jax.__version__)
    smoke = Smoke(args)
    (_four_chips if args.chips == 4 else _single_chip)(smoke)
    stats = compile_cache.stats()
    _emit(cache_hit=stats["hits"] > 0, cache_hits=stats["hits"], cache_misses=stats["misses"],
          compile_s=stats["compile_s"])
    if args.rehearse:
        print("rehearsal passed (CPU, smoke size): not a chip result", flush=True)
        return 0
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {"platform": d[0].platform,
                                            "kind": d[0].device_kind, "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
