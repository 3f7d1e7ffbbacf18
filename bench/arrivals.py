"""Arrival schedules for the traffic mixes under ``bench/traffic/<mix>.json``.

One general generator reads every mix. Parameters:

* ``process``: the name of one stream's arrival process, a file of its own
  under ``bench/processes/`` (``poisson``, ``mmpp``), which reads the
  mix's further parameters;
* ``aggregate_hz``: the mean offered rate of all streams together, split
  equally over the configuration's streams.

Every seed gives the same amount of work in another order, so that runs
with different seeds differ only as much as the arrivals' timing makes
them: each stream gets exactly ``round(rate * seconds)`` arrivals (a
Poisson process conditioned on its count is uniform order statistics),
and under ``mmpp`` each stream's burst and quiet dwells are rescaled to
their expected shares of the window before the arrivals are placed in
them. This is the copy of ``serve/traffic.arrival_times`` (Poisson and
bursty processes) that the yardstick keeps, with counts fixed per seed.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

import numpy as np


@dataclasses.dataclass
class Arrival:
    t_due: float  # seconds after the window opens
    stream: str
    pool: int  # index of the input frame in the run's pool
    sampled: bool = False  # compared against the reference after the window
    t_offer: float = float("nan")
    decision: str = ""
    latency_s: float = float("nan")  # executor's submit -> completion
    batch: int = 0
    output: object = None

    @property
    def done(self) -> bool:
        return self.latency_s == self.latency_s

    @property
    def latency_due_s(self) -> float:
        """Due time to completion: the offer's lateness plus the program's
        own submit-to-completion latency."""
        return (self.t_offer - self.t_due) + self.latency_s


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def process(name: str):
    """The arrival process ``bench/processes/<name>.py``: its ``times(rng,
    rate_hz, seconds, params)`` gives one stream's sorted arrival times."""
    if name not in _PROCESSES:
        path = pathlib.Path(__file__).resolve().parent / "processes" / f"{name}.py"
        if not path.is_file():
            raise ValueError(f"unknown traffic process {name!r}: no {path}")
        spec = importlib.util.spec_from_file_location(f"bench_process_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PROCESSES[name] = mod.times
    return _PROCESSES[name]


_PROCESSES: dict = {}


def schedule(mix: dict, streams: list[str], seed: int, seconds: float, pool: int,
             salt: int = 0) -> list[Arrival]:
    """The time-ordered arrivals of one run: every stream's process,
    merged, each arrival carrying a pool frame drawn from the seed.
    ``salt`` draws another schedule from the same seed."""
    rate = float(mix["aggregate_hz"]) / len(streams)
    times = process(mix["process"])
    events = []
    for si, name in enumerate(streams):
        ts = times(_rng(seed, salt, 1, si), rate, seconds, mix)
        events.extend((float(t), si, name) for t in ts if t < seconds)
    events.sort()
    picks = _rng(seed, salt, 2).integers(0, pool, len(events))
    return [Arrival(t, name, int(k)) for (t, _, name), k in zip(events, picks)]


def mark_sample(arrivals: list[Arrival], model_of: dict, per_model: int, seed: int) -> None:
    """Mark up to ``per_model`` arrivals of each model, drawn from the seed,
    for the comparison with the reference."""
    rng = _rng(seed, 3)
    by_model: dict = {}
    for i, a in enumerate(arrivals):
        by_model.setdefault(model_of[a.stream], []).append(i)
    for idx in by_model.values():
        for i in rng.permutation(idx)[:per_model]:
            arrivals[int(i)].sampled = True
