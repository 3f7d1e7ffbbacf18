"""One run of one cell: build, warm up, drive the open-loop window, check.

The system under test is the serving stack as users build it,
``repro.serve.build_server`` with the configuration file's ``build``
arguments. The benchmark makes the weights (``reference.make_weights``)
and the frames (``inputs.pool``) from the seed and hands them to it; the
window drives ``server.offer`` and ``server.tick`` itself:

* every frame due by now is offered before the next tick, and the offer's
  time is recorded, so latency runs from the due time (the offer's
  lateness plus the program's own submit-to-completion latency);
* after the window nothing more is offered and ticks go on until every
  admitted frame has completed;
* outputs of a seeded sample of the window's arrivals are kept, all others
  are released as they complete, and the sample is compared with the
  reference once the program's state is freed (``check.py``).
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

from . import arrivals, check, flops, inputs, reference

SAMPLE_PER_MODEL = 96  # arrivals per model compared with the reference
DRAIN_S = 30.0  # longest the window's admitted frames may take to complete
TRACE_S = 2.0  # length of the traced tail that follows the window in --trace 1 runs
STALL_S = 0.05  # a pass of the window's loop longer than this is recorded as a host stall
SPANS_OFF = contextlib.nullcontext()


@contextlib.contextmanager
def gc_pauses(out: list):
    """Append ``(generation, seconds)`` of every garbage collection the
    interpreter makes inside the block to ``out``."""
    started = {}

    def hook(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            out.append((info["generation"], time.perf_counter() - started.pop("t")))

    gc.callbacks.append(hook)
    try:
        yield out
    finally:
        gc.callbacks.remove(hook)


def _model_cfgs(config: dict, overrides: dict | None) -> list[dict]:
    out = []
    for m in config["models"]:
        m = dict(m)
        for k, v in (overrides or {}).items():
            if k in m:
                m[k] = v
        out.append(m)
    return out


class Cell:
    """The serving stack of one configuration, with the benchmark's own
    bookkeeping of every offer it makes."""

    def __init__(self, config: dict, overrides: dict | None = None):
        """``overrides`` (``img_size``, ``base``) shrink the models for the
        benchmark's own tests on the CPU."""
        from repro.serve import BatchConfig, build_server

        self.config = config
        self.models = _model_cfgs(config, overrides)
        kw = dict(config["build"])
        if overrides:
            kw.update(img=overrides.get("img_size", kw["img"]), base=overrides.get("base", kw["base"]))
        if kw.get("batching"):
            kw["batching"] = BatchConfig(**kw["batching"])
        self.deadline_s = kw["deadline_ms"] / 1e3
        self.bundle = build_server(**kw)
        self.server = self.bundle.server
        self.executors = [s.executor for s in getattr(self.server, "servers", [self.server])]
        self.streams = [s.name for s in self.bundle.streams]
        self.model_of = {s.name: s.model_index for s in self.bundle.streams}
        self.flops = {s.name: flops.per_frame(self.models[s.model_index]) for s in self.bundle.streams}
        self.admitted: dict[str, list] = {s: [] for s in self.streams}
        self.cursor = [0] * len(self.executors)
        self.weights = None

    # -- weights --------------------------------------------------------------

    def load_weights(self, seed: int) -> None:
        """Make every model's weights from the seed and serve those."""
        import jax

        self.weights = reference.make_weights(self.models, seed)
        for m, w in zip(self.bundle.models, self.weights):
            want = jax.tree.map(lambda a: (a.shape, a.dtype), m.params)
            got = jax.tree.map(lambda a: (a.shape, a.dtype), w)
            if want != got:
                raise ValueError(f"{m.name}: the program's parameters are not the reference's")
            m.params = w
        if len(self.executors) > 1:
            pool, n = self.server.pool, len(self.executors)
            for r, ex in enumerate(self.executors):
                ex.engine_params = pool.place_params(self.bundle.models, r, n)

    # -- offers and completions ------------------------------------------------

    def offer(self, stream: str, frame, arrival=None) -> str:
        decision = self.server.offer(stream, frame)
        if decision != "drop":
            # the executor numbers a stream's admitted frames in order
            self.admitted[stream].append(arrival)
        if arrival is not None:
            arrival.decision = decision
        return decision

    def collect(self) -> None:
        """Fold new completions into their arrivals; keep sampled outputs,
        release every other."""
        for k, ex in enumerate(self.executors):
            new = ex.completions[self.cursor[k]:]
            self.cursor[k] = len(ex.completions)
            for c in new:
                a = self.admitted[c.stream][c.frame_id]
                if a is not None:
                    a.latency_s, a.batch = c.latency_s, c.batch
                    if a.sampled:
                        a.output = c.output
                c.output = None
            for v in ex.outputs.values():
                v.clear()

    def drain(self) -> None:
        while self.server.executor.pending:
            self.server.tick()
            self.collect()

    # -- warm-up ----------------------------------------------------------------

    def warm_up(self, frame, rounds: int = 2) -> None:
        """Compile and run every shape the window can use, through the
        public entry: for each replica's streams of each model, flights of
        every size the coalescer can form (one, for models it does not
        batch), then the degraded single-segment route that admission
        sheds to under pressure. The second round runs warm, so the
        coalescer's service-time estimates start from steady values."""
        bc = self.executors[0].batching
        for s in self.streams:  # fixes each stream's replica
            self.offer(s, frame)
        self.drain()
        router = getattr(self.server, "router", None)
        groups: dict = {}
        for s in self.streams:
            groups.setdefault(router.replica_of(s) if router else 0, []).append(s)
        for _ in range(rounds):
            for names in groups.values():
                for mi in range(len(self.bundle.models)):
                    mine = [s for s in names if self.model_of[s] == mi]
                    merges = bc.enabled and self.executors[0].merge_batches[mi]
                    for k in range(1, (bc.max_batch if merges else 1) + 1):
                        for j in range(k):
                            self.offer(mine[j % len(mine)], frame)
                        self.drain()
                    for j in range(64 * len(mine)):
                        if self.offer(mine[j % len(mine)], frame) in ("shed_route", "drop"):
                            break
                    self.drain()

    # -- the window ---------------------------------------------------------------

    def window(self, schedule, frames, seconds: float, trace_dir: str | None = None) -> dict:
        """Offer ``schedule`` open loop and tick until every admitted frame
        has completed; returns timings.

        With ``trace_dir`` the profiler records ``[seconds, seconds +
        TRACE_S)``, a tail of further arrivals after the window: neither
        the profiler's start nor its stop stalls the window, and what
        tracing costs the host stays out of the window's numbers."""
        import jax

        server, clock = self.server, time.perf_counter
        ticks0 = [len(ex.tick_stats) for ex in self.executors]
        ticks1 = report = None
        server.reset_metrics()
        n_window = sum(a.t_due < seconds for a in schedule)
        trace_at = (seconds, seconds + TRACE_S) if trace_dir else None
        traced = None  # [start, end] of the traced tail, seconds after t0
        spans = lambda name: SPANS_OFF  # noqa: E731
        i, n, backlog = 0, len(schedule), None
        pauses: list = []
        stalls: list = []
        t0 = clock()
        with gc_pauses(pauses):
            while True:
                lap, cpu, pcpu = clock(), time.thread_time(), time.process_time()
                now = lap - t0
                ticked = False
                if trace_at and traced is None and now >= trace_at[0]:
                    ticks1 = [len(ex.tick_stats) for ex in self.executors]
                    report = server.report()  # the window's counters, before the tail's
                    opts = jax.profiler.ProfileOptions()
                    opts.host_tracer_level, opts.python_tracer_level = 1, 0
                    opts.enable_hlo_proto = False
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    traced = [clock() - t0, None]
                    spans = jax.profiler.TraceAnnotation
                elif traced and traced[1] is None and now >= trace_at[1]:
                    jax.profiler.stop_trace()
                    traced[1] = clock() - t0
                    spans = lambda name: SPANS_OFF  # noqa: E731
                if i < n and schedule[i].t_due <= now:
                    with spans("bench.offer"):
                        while i < n and schedule[i].t_due <= clock() - t0:
                            a = schedule[i]
                            a.t_offer = clock() - t0
                            self.offer(a.stream, frames[a.pool], a)
                            i += 1
                            if i == n_window:
                                backlog = server.executor.pending
                t_offered = clock()
                if server.executor.pending:
                    with spans("bench.tick"):
                        server.tick()
                    with spans("bench.collect"):
                        self.collect()
                    ticked = True
                else:
                    nxt = schedule[i].t_due if i < n else None
                    if trace_at and (traced is None or traced[1] is None):
                        edge = trace_at[0] if traced is None else trace_at[1]
                        nxt = edge if nxt is None else min(nxt, edge)
                    if nxt is None:
                        break
                    with spans("bench.wait"):
                        time.sleep(max(0.0, min(1e-3, nxt - (clock() - t0))))
                if clock() - lap > STALL_S:
                    stalls.append(self._stall(lap - t0, clock() - lap, t_offered - lap, ticked,
                                              time.thread_time() - cpu, time.process_time() - pcpu))
                if now > seconds + (TRACE_S if trace_at else 0.0) + DRAIN_S:
                    break
        end = clock() - t0
        server.finish()
        stop = ticks1 or [len(ex.tick_stats) for ex in self.executors]
        return {
            "end_s": end,
            "report": report or server.report(),
            "traced_s": traced,
            "backlog_at_horizon": backlog if backlog is not None else 0,
            "gc_pauses": pauses,
            "stalls": stalls,
            "ticks": [t for ex, a, b in zip(self.executors, ticks0, stop) for t in ex.tick_stats[a:b]],
        }

    def _stall(self, at_s, wall_s, offer_s, ticked, cpu_s, process_cpu_s) -> dict:
        """One pass of the window's loop that took over ``STALL_S``: where
        the time went, the main thread's and the whole process's CPU time
        over it, and the last tick's wall and blocked time. A pass whose
        thread ran little and did not block was held off the CPU."""
        last = [ex.tick_stats[-1] for ex in self.executors if ticked and ex.tick_stats]
        return {"at_s": at_s, "wall_s": wall_s, "offer_s": offer_s, "phase": "tick" if ticked else "wait",
                "tick_wall_s": sum(t.wall_s for t in last), "tick_blocked_s": sum(t.blocked_s for t in last),
                "thread_cpu_s": cpu_s, "process_cpu_s": process_cpu_s}

    def close(self) -> None:
        """Release the program's state; the weights stay for the reference."""
        self.bundle.close()
        self.bundle = self.server = self.executors = None
        gc.collect()


def memory_peak(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    """One run of the cell in ``spec``, from a fresh build; returns the run record."""
    from repro.launch import compile_cache

    cs0 = compile_cache.stats()
    t_build = time.perf_counter()
    cell = Cell(spec["config"])
    t_built = time.perf_counter()
    rec = measure(cell, spec, seed, seconds, trace, t_start, cs0)
    rec["setup_phases_s"] = {"imports_and_backend": t_build - t_start, "build_server": t_built - t_build,
                             **rec["setup_phases_s"]}
    return rec


def measure(cell: Cell, spec: dict, seed: int, seconds: float, trace: bool = False,
            t_start: float | None = None, cs0: dict | None = None, after_window=None,
            release: bool = True) -> dict:
    """Weights, frames and arrivals from the seed, warm-up, the window and
    the comparison, on a built ``cell``.

    ``after_window`` (called with the cell, the arrivals and the frames)
    and ``release=False`` serve the benchmark's own tests, which run it on
    the CPU at a small size with the timed path broken underneath."""
    import jax

    from repro.launch import compile_cache

    cs0 = cs0 or compile_cache.stats()
    stamps = [("built", time.perf_counter())]
    cell.load_weights(seed)
    stamps.append(("weights", time.perf_counter()))
    frames = inputs.pool(seed, cell.models[0]["img_size"])
    sched = arrivals.schedule(spec["mix"], cell.streams, seed, seconds, len(frames))
    arrivals.mark_sample(sched, cell.model_of, SAMPLE_PER_MODEL, seed)
    tail = []
    if trace:
        tail = arrivals.schedule(spec["mix"], cell.streams, seed, TRACE_S, len(frames), salt=1)
        for a in tail:
            a.t_due += seconds
    stamps.append(("inputs", time.perf_counter()))
    cell.warm_up(frames[0])
    stamps.append(("warm_up", time.perf_counter()))
    cs1 = compile_cache.stats()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    t_setup = time.perf_counter()
    try:
        timing = cell.window(sched + tail, frames, seconds, trace_dir)
        cs2 = compile_cache.stats()
        report = timing["report"]
        peak = memory_peak(spec["cell"]["chips"])
        if after_window is not None:
            after_window(cell, sched, frames)
        outputs = {id(a): jax.device_get(a.output) for a in sched if a.output is not None}
        for a in sched:
            a.output = outputs.get(id(a))
        if release:
            cell.close()
        reduced = None
        if trace_dir:
            from . import trace as trace_mod

            reduced = trace_mod.reduce(trace_mod.load(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t_check = time.perf_counter()
    checks = check.compare(spec["config"], cell.models, cell.weights, frames, sched, cell.model_of)
    t_checked = time.perf_counter()
    return {
        "seconds": seconds,
        "chips": spec["cell"]["chips"],
        "deadline_s": cell.deadline_s,
        "arrivals": sched,
        "tail": tail,
        "flops": cell.flops,
        "ticks": timing["ticks"],
        "report": report,
        "trace": reduced,
        "traced_s": timing["traced_s"],
        "backlog_at_horizon": timing["backlog_at_horizon"],
        "gc_pauses": timing["gc_pauses"],
        "stalls": timing["stalls"],
        "drain_s": timing["end_s"] - seconds,
        "setup_s": None if t_start is None else t_setup - t_start,
        "setup_phases_s": {k: t - p for (k, t), (_, p) in zip(stamps[1:], stamps)},
        "setup_compile_s": cs1["compile_s"] - cs0["compile_s"],
        "window_compiles": (cs2["hits"] + cs2["misses"]) - (cs1["hits"] + cs1["misses"]),
        "memory_peak_bytes": peak,
        "checks": checks,
        "check_s": t_checked - t_check,
    }
