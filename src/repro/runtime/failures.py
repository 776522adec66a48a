"""Simulated failure / straggler injection for fault-tolerance testing.

Real TPU fleets lose chips and hosts; without hardware we inject the same
*control-flow* events so the driver's recovery paths are genuinely
exercised (DESIGN.md §5): a ``ChipFailure`` aborts the step loop exactly
the way a XLA device error would surface (an exception out of the host
loop), and ``StragglerClock`` skews per-step wall times so the EWMA
detector has something to find.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, Optional, Tuple

import numpy as np


class ChipFailure(RuntimeError):
    """Stands in for a device/host loss surfaced to the host loop."""


class TickFailure(RuntimeError):
    """Stands in for a transient device error out of the fused decode
    tick (the serving twin of :class:`ChipFailure`).  The engine retries
    the tick up to ``EngineConfig.max_retries`` times with backoff, then
    re-raises."""


@dataclasses.dataclass
class FailureInjector:
    """Deterministic scripted failures: fail at the given steps (once each)."""

    fail_at_steps: tuple = ()
    seed: int = 0
    random_rate: float = 0.0  # additional iid failure probability per step

    def __post_init__(self):
        self._rng = np.random.Generator(np.random.Philox(self.seed))
        self._fired = set()

    def check(self, step: int):
        if step in self.fail_at_steps and step not in self._fired:
            self._fired.add(step)
            raise ChipFailure(f"simulated chip loss at step {step}")
        if self.random_rate and self._rng.random() < self.random_rate:
            raise ChipFailure(f"simulated random chip loss at step {step}")


@dataclasses.dataclass(eq=False)  # identity eq/hash: EngineConfig is frozen
class ServeFaultInjector:
    """Deterministic scripted serving faults, keyed by decode-tick number.

    Threaded through ``EngineConfig.injector``; the engine consults it at
    each tick boundary (tick N = the N'th fused decode tick of the run,
    0-based).  One injector scripts one run — build a fresh one per
    ``Engine.run`` (events are consumed; ``reset()`` re-arms).  Engines
    with an injector that call ``warmup`` re-arm it with ``reset()``
    afterwards (the warm-up runs the same loop and consumes the script).

    * ``fail_ticks`` — multiset of tick numbers; each occurrence raises
      one :class:`TickFailure` before that tick executes (so
      ``(3, 3, 3)`` exhausts a 2-retry budget deterministically).
    * ``poison`` — ``{tick: (rid, ...)}``: write NaN into those
      requests' KV cache rows (``serving.resilience.poison_slot_cache``)
      right before the tick; rids not yet active are held until they
      are.
    * ``squeeze`` — ``{tick: n}``: seize ``n`` free pages from a paged
      arena (simulated memory pressure); ``release_ticks`` gives them
      back.  Ignored by slot pools.
    * ``skew`` — ``{tick: seconds}``: jump the engine clock forward —
      deadline expiry becomes testable without real sleeps.
    * ``cancels`` — ``{tick: (rid, ...)}``: call ``Engine.cancel``.
    """

    fail_ticks: Tuple[int, ...] = ()
    poison: Dict[int, tuple] = dataclasses.field(default_factory=dict)
    squeeze: Dict[int, int] = dataclasses.field(default_factory=dict)
    release_ticks: Tuple[int, ...] = ()
    skew: Dict[int, float] = dataclasses.field(default_factory=dict)
    cancels: Dict[int, tuple] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.reset()

    def reset(self) -> None:
        """Re-arm every scripted event (for reusing one injector)."""
        self._fail = Counter(self.fail_ticks)
        self._applied: set = set()

    def take_failure(self, tick: int) -> bool:
        """Consume one scripted failure for this tick, if any remain.
        Called once per tick *attempt*, so retries of the same tick keep
        consuming occurrences."""
        if self._fail.get(tick, 0) > 0:
            self._fail[tick] -= 1
            return True
        return False

    def events_at(self, tick: int) -> Optional[dict]:
        """The non-exception events scripted for this tick, consumed
        exactly once (idle engine-loop passes at the same tick return
        None on re-query)."""
        if tick in self._applied:
            return None
        self._applied.add(tick)
        ev: dict = {}
        if tick in self.skew:
            ev["skew"] = float(self.skew[tick])
        if tick in self.cancels:
            ev["cancel"] = tuple(self.cancels[tick])
        if tick in self.squeeze:
            ev["squeeze"] = int(self.squeeze[tick])
        if tick in self.release_ticks:
            ev["release"] = True
        if tick in self.poison:
            ev["poison"] = tuple(self.poison[tick])
        return ev or None


@dataclasses.dataclass
class StragglerClock:
    """Synthetic per-step durations with a persistent slow host.

    ``sample(step)`` returns the simulated step time: baseline noise, plus
    a multiplicative slowdown when the scripted straggler is active.
    """

    base: float = 1.0
    jitter: float = 0.05
    slow_from: Optional[int] = None
    slow_factor: float = 3.0
    seed: int = 1

    def __post_init__(self):
        self._rng = np.random.Generator(np.random.Philox(self.seed))

    def sample(self, step: int) -> float:
        t = self.base * (1.0 + self.jitter * self._rng.standard_normal())
        if self.slow_from is not None and step >= self.slow_from:
            t *= self.slow_factor
        return max(t, 1e-6)


@dataclasses.dataclass
class StragglerDetector:
    """EWMA step-time monitor: flags a persistent outlier host/step stream.

    Mirrors production practice: alert when the instantaneous step time
    exceeds ``threshold`` x the EWMA for ``patience`` consecutive steps —
    the driver then triggers the elastic re-mesh path.
    """

    alpha: float = 0.1
    threshold: float = 2.0
    patience: int = 3

    ewma: Optional[float] = None
    strikes: int = 0

    def observe(self, dt: float) -> bool:
        if self.ewma is None:
            self.ewma = dt
            return False
        is_slow = dt > self.threshold * self.ewma
        self.strikes = self.strikes + 1 if is_slow else 0
        # EWMA tracks only non-outlier samples so a straggler can't hide
        # by dragging the baseline up.
        if not is_slow:
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return self.strikes >= self.patience
