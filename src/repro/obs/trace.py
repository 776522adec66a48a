"""Ring-buffered span/event recorder with an injectable monotonic clock.

Design constraints (``tests/test_obs.py`` counts them on every run):

* **Host-side only** — every record is built from values the caller
  already holds (slot ids, rids, counts); recording never touches a
  device array, so tracing adds zero device->host transfers.
* **Tuples in a deque** — one event is one plain tuple appended to a
  ``deque(maxlen=capacity)``; no objects, no locks, no I/O.  When the
  ring wraps, the oldest events drop and ``dropped`` counts them (the
  exporter surfaces the count so a truncated trace is never mistaken
  for a complete one).
* **Injectable clock** — ``bind_clock`` swaps the timestamp source;
  the engine binds its run clock (wall time + injected skew), so spans
  move with the chaos harness's clock-skew faults exactly like
  deadlines do, and tests can bind a fake clock for determinism.

Event forms (``kind`` first; ``track`` is ``(group, index)``, e.g.
``("req", 3)`` / ``("slot", 0)`` / ``("engine", 0)``):

* ``("span", name, track, t0, dur, args)`` — a completed interval.
* ``("inst", name, track, t, args)`` — a point event.
* ``("ctr", name, track, t, value)`` — a counter sample.

``phase(name, **args)`` is a context manager for the engine's host
phases: it records ``engine.<name>`` as a span on ``ENGINE_TRACK`` and
opens a ``jax.profiler.TraceAnnotation`` of the same name, so the same
interval also lands on the profiler's host plane, on the device trace's
clock (a no-op while no profiler session runs).  The span closes on every
exit path, exceptions included.

``begin``/``end`` pair open intervals by ``(track, name)`` — ``end``
on a never-begun pair is a no-op (returns ``None``), which lets the
engine close "whichever of queued/decode is open" unconditionally on
every finish path.  ``open_spans()`` exposes what never closed; the
span-chain validator asserts it is empty after a run.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

from jax.profiler import TraceAnnotation

Track = Tuple[str, int]

ENGINE_TRACK: Track = ("engine", 0)
POOL_TRACK: Track = ("pool", 0)

SPAN = "span"
INSTANT = "inst"
COUNTER = "ctr"

PHASE_PREFIX = "engine."


class Tracer:
    """See module docstring.  ``capacity`` bounds the ring buffer;
    ``clock`` defaults to ``time.perf_counter`` until something binds a
    better one."""

    def __init__(self, capacity: int = 1 << 16, clock=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.events: deque = deque(maxlen=capacity)
        self.dropped = 0
        self._clock = clock if clock is not None else time.perf_counter
        self._open: Dict[Tuple[Track, str], Tuple[float, Optional[dict]]] = {}

    # -- clock ---------------------------------------------------------------

    def bind_clock(self, clock) -> "Tracer":
        """Swap the timestamp source (engine run clock, fake test clock).
        Returns self so ``Tracer().bind_clock(c)`` chains."""
        self._clock = clock
        return self

    def now(self) -> float:
        return self._clock()

    # -- recording -----------------------------------------------------------

    def _push(self, ev: tuple) -> None:
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    def span(self, name: str, track: Track, t0: float,
             t1: Optional[float] = None, **args: Any) -> None:
        """Record a completed interval; ``t1=None`` means "now"."""
        if t1 is None:
            t1 = self._clock()
        self._push((SPAN, name, track, t0, t1 - t0, args or None))

    def begin(self, name: str, track: Track, **args: Any) -> None:
        """Open an interval keyed ``(track, name)``; a re-begin of an
        already-open pair overwrites it (the old begin is lost)."""
        self._open[(track, name)] = (self._clock(), args or None)

    def end(self, name: str, track: Track, t: Optional[float] = None,
            **args: Any) -> Optional[float]:
        """Close an open interval and record the span; no-op (None) when
        the pair was never begun.  ``t=None`` means "now".  Returns the
        duration."""
        opened = self._open.pop((track, name), None)
        if opened is None:
            return None
        t0, bargs = opened
        if bargs:
            merged = dict(bargs)
            merged.update(args)
            args = merged
        t1 = self._clock() if t is None else t
        self._push((SPAN, name, track, t0, t1 - t0, args or None))
        return t1 - t0

    def phase(self, name: str, **args: Any) -> "_Phase":
        """``with tracer.phase("admit", tick=3):`` -- see module docstring."""
        return _Phase(self, PHASE_PREFIX + name, args or None)

    def instant(self, name: str, track: Track = ENGINE_TRACK,
                t: Optional[float] = None, **args: Any) -> None:
        if t is None:
            t = self._clock()
        self._push((INSTANT, name, track, t, args or None))

    def counter(self, name: str, value: float,
                track: Track = ENGINE_TRACK,
                t: Optional[float] = None) -> None:
        if t is None:
            t = self._clock()
        self._push((COUNTER, name, track, t, value))

    # -- inspection ----------------------------------------------------------

    def open_spans(self) -> Dict[Tuple[Track, str], float]:
        """``(track, name) -> begin time`` for every begun-but-unclosed
        interval — must be empty after a clean engine run."""
        return {k: v[0] for k, v in self._open.items()}

    def __len__(self) -> int:
        return len(self.events)

    def clear(self) -> None:
        """Drop all recorded events and open intervals (e.g. after a
        warmup run, so the exported trace covers only the real one)."""
        self.events.clear()
        self._open.clear()
        self.dropped = 0


class _Phase:
    """One host phase: a span on the engine clock and a profiler
    annotation over the same interval."""

    __slots__ = ("tracer", "name", "args", "t0", "ann")

    def __init__(self, tracer: Tracer, name: str, args: Optional[dict]):
        self.tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self) -> "_Phase":
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = self.tracer._clock()
        return self

    def __exit__(self, *exc) -> None:
        tr = self.tracer
        t0 = self.t0
        tr._push((SPAN, self.name, ENGINE_TRACK, t0, tr._clock() - t0,
                  self.args))
        self.ann.__exit__(*exc)
