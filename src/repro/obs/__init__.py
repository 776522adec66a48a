"""Serving observability: request-lifecycle tracing, metrics, export.

The paper's whole argument is a latency/cost trade (Goldschmidt
iterations vs. hardware), and arXiv:2305.03728 shows GS error is
attributable per *stage*; this package attributes serving latency and
numeric events per stage the same way — which request spent how long
where (queued / prefill / decode), which kernel fell back, when a
quarantine or preemption fired — without adding a single device->host
transfer (every event is recorded host-side from data the engine
already holds).

* :mod:`repro.obs.trace` — :class:`Tracer`, a ring-buffered span/event
  recorder with an injectable monotonic clock (the engine binds its own
  skew-adjusted clock, so the chaos harness's clock-skew faults move
  the trace timeline the way they move deadlines).  ``Tracer.phase``
  spans the engine's host phases and mirrors each into the profiler's
  trace as an ``engine.<phase>`` annotation, on the device trace's clock.
* :mod:`repro.obs.metrics` — histogram registry with
  p50/p95/p99 summaries; :func:`summarize` backs the real TTFT and
  inter-token-latency distributions on ``ServeMetrics``.
* :mod:`repro.obs.export` — JSONL event log plus Chrome-trace/Perfetto
  JSON (one track per request, one per slot, counter tracks for the
  engine) loadable in ``ui.perfetto.dev``; span-chain and structural
  validators back the ``obs-smoke`` CI gate.

``launch/serve.py --trace-out`` wires a tracer through a serving run
and ``python -m repro.launch.obsview`` summarizes the exported file.
"""

from repro.obs.export import (load_events, request_chains,  # noqa: F401
                              to_chrome_trace, validate_chains,
                              validate_chrome_trace, write_chrome_trace,
                              write_jsonl)
from repro.obs.metrics import (Histogram, MetricsRegistry,  # noqa: F401
                               percentile, summarize)
from repro.obs.trace import ENGINE_TRACK, POOL_TRACK, Tracer  # noqa: F401

__all__ = [
    "Tracer", "ENGINE_TRACK", "POOL_TRACK",
    "Histogram", "MetricsRegistry",
    "percentile", "summarize",
    "to_chrome_trace", "write_chrome_trace", "write_jsonl", "load_events",
    "request_chains", "validate_chains", "validate_chrome_trace",
]
