"""Spans and counters inside the serving tick.

One ``SpanRecorder`` belongs to each ``MultiStreamServer``, which shares it
with its executor. It is off by default: ``span(name, **attrs)`` then
returns one shared no-op context and reads no clock. When it is on
(``enable()``), every span records its name, start, end, parent span and
attributes, and also:

* per-name counters: count, total seconds, self seconds (the span's
  duration less the time its children cover) and the longest;
* every span longer than ``long_s`` with its ancestors, kept per tree of
  spans (a root span and what ran inside it) in a record of at most
  ``long_keep`` trees: what a host stall was doing, over a whole window,
  in bounded memory;
* the last ``ring`` spans, in the order they ended;
* ``python.gc`` spans, tagged with the generation, for each garbage
  collection the interpreter makes: one span per collection in the whole
  process, however many recorders are on, recorded by the recorder whose
  span is open (the first one switched on where none is).

Each span is also written as a ``jax.profiler.TraceAnnotation`` with its
attributes, which the profiler records while it runs: the spans then sit
on the same clock as the device's operations. A replica's recorder (a
fleet's, ``replica=r``) adds ``replica`` to every span it writes, unless
the span set its own. Spans that serve frames carry ``frames``, the frames'
``<stream>/<frame id>`` keys joined by ``;``, so one frame's spans share
its identifier.

The recorders expect one thread: the one that ticks the servers.
"""
from __future__ import annotations

import gc
import itertools
import time
from collections import deque

from jax.profiler import TraceAnnotation

LONG_S = 0.010  # spans longer than this are kept, with their ancestors
LONG_KEEP = 512  # trees of long spans kept
RING = 4096  # most recent spans kept


class Span:
    """One recorded span; the context manager that records it."""

    __slots__ = ("rec", "id", "name", "parent", "depth", "t0", "t1", "attrs", "child_s", "ann", "kept")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.child_s = 0.0
        self.kept = None  # long spans of this tree, on a root span
        self.ann = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    @property
    def self_s(self) -> float:
        return self.t1 - self.t0 - self.child_s

    def note(self, **attrs) -> None:
        """Add attributes known only once the span's work is done."""
        self.attrs.update(attrs)
        if self.ann is not None:
            self.ann.set_metadata(**attrs)

    def __enter__(self) -> "Span":
        rec = self.rec
        stack = rec._stack
        self.parent = stack[-1].id if stack else None
        self.depth = len(stack)
        self.id = next(rec._ids)
        if TraceAnnotation.is_enabled():
            self.ann = TraceAnnotation(self.name, **self.attrs)
            self.ann.__enter__()
        stack.append(self)
        self.t0 = rec.clock()
        return self

    def __exit__(self, *exc) -> None:
        rec = self.rec
        self.t1 = rec.clock()
        if self.ann is not None:
            if rec.replica is not None and "replica" not in self.attrs:
                self.ann.set_metadata(replica=rec.replica)
            self.ann.__exit__(None, None, None)
            self.ann = None
        rec._stack.pop()
        rec._close(self)

    def as_dict(self, t0: float = 0.0) -> dict:
        tag = {} if self.rec.replica is None else {"replica": self.rec.replica}
        return {"id": self.id, "name": self.name, "parent": self.parent, "depth": self.depth,
                "start_s": self.t0 - t0, "dur_s": self.dur, "self_s": self.self_s, **tag, **self.attrs}


class _Off:
    """The shared context ``span`` returns while the recorder is off."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def note(self, **attrs) -> None:
        pass


OFF = _Off()

_ON: list["SpanRecorder"] = []  # recorders on, in the order they were switched on
_gc_span: Span | None = None  # the collection in progress


def _on_gc(phase: str, info: dict) -> None:
    """The process's one ``gc.callbacks`` hook while any recorder is on."""
    global _gc_span
    if phase == "start":
        rec = next((r for r in _ON if r._stack), _ON[0])
        _gc_span = Span(rec, "python.gc", {"generation": info["generation"]})
        _gc_span.__enter__()
    elif _gc_span is not None:
        s, _gc_span = _gc_span, None
        s.__exit__()


class SpanRecorder:
    def __init__(self, long_s: float = LONG_S, long_keep: int = LONG_KEEP, ring: int = RING,
                 clock=time.perf_counter, replica: int | None = None):
        self.enabled = False
        self.long_s = long_s
        self.clock = clock
        self.replica = replica
        self._stack: list[Span] = []
        self._ids = itertools.count(1)
        self._long: deque[list[Span]] = deque(maxlen=long_keep)
        self._ring: deque[Span] = deque(maxlen=ring)
        self.recorded = 0  # spans ended since the last reset
        self.long_dropped = 0  # trees of long spans the record let go since the last reset
        self.counters: dict[str, list[float]] = {}  # name -> [count, total_s, self_s, max_s]
        self.t_reset = 0.0

    # -- switching ------------------------------------------------------------

    def enable(self) -> None:
        if not self.enabled:
            self.enabled = True
            if not _ON:
                gc.callbacks.append(_on_gc)
            _ON.append(self)
            self.reset()

    def disable(self) -> None:
        if self.enabled:
            self.enabled = False
            _ON.remove(self)
            if not _ON:
                gc.callbacks.remove(_on_gc)

    def reset(self) -> None:
        """Forget every recorded span and counter (spans still open are
        recorded when they end)."""
        self._long.clear()
        self._ring.clear()
        self.counters = {}
        self.recorded = 0
        self.long_dropped = 0
        self.t_reset = self.clock() if self.enabled else 0.0

    # -- recording ------------------------------------------------------------

    def span(self, name: str, **attrs):
        """A context recording ``name`` around its block; off, the shared no-op."""
        if not self.enabled:
            return OFF
        return Span(self, name, attrs)

    def _close(self, s: Span) -> None:
        stack = self._stack
        d = s.t1 - s.t0
        if stack:
            stack[-1].child_s += d
        try:
            c = self.counters[s.name]
        except KeyError:
            c = self.counters[s.name] = [0, 0.0, 0.0, 0.0]
        c[0] += 1
        c[1] += d
        c[2] += d - s.child_s
        if d > c[3]:
            c[3] = d
        self._ring.append(s)
        self.recorded += 1
        if d > self.long_s:
            # a long span's ancestors are longer still: the tree's root
            # collects its long spans and the record keeps whole trees
            root = stack[0] if stack else s
            if root.kept is None:
                root.kept = []
            root.kept.append(s)
        if not stack and s.kept is not None:
            if len(self._long) == self._long.maxlen:
                self.long_dropped += 1
            self._long.append(s.kept)
            s.kept = None

    # -- reading --------------------------------------------------------------

    def since(self, mark: int) -> list[Span]:
        """Spans ended since ``recorded`` read ``mark`` (as many as the ring
        still holds)."""
        n = min(self.recorded - mark, len(self._ring))
        return list(itertools.islice(self._ring, len(self._ring) - n, None)) if n > 0 else []

    def recent(self) -> list[Span]:
        return list(self._ring)

    def long_spans(self) -> list[Span]:
        """Kept long spans, tree by tree, each tree's root last."""
        return [s for tree in self._long for s in tree]

    def summary(self) -> dict:
        """Counters and long spans since the last reset, JSON-able; span
        starts are seconds after the reset."""
        return {
            "counters": {n: {"count": int(c[0]), "total_s": c[1], "self_s": c[2], "max_s": c[3]}
                         for n, c in sorted(self.counters.items())},
            "long_s": self.long_s,
            "long_dropped": self.long_dropped,
            "long": [s.as_dict(self.t_reset) for s in self.long_spans()],
        }
