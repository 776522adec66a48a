"""Summarize an exported serving trace on the terminal.

  PYTHONPATH=src python -m repro.launch.obsview serve_trace.json

Reads either export form (Chrome-trace JSON or JSONL — see
``repro.obs.export``) and prints the run at a glance: request count and
finish-reason mix, per-phase latency distributions (queued / prefill /
decode / tick), the engine's host phases (``engine.*``: p50 / p95 and
the total per phase), counter peaks, incident counts (preempt / retry /
quarantine / poison), and — when the exporter embedded the run's
``ServeMetrics`` in the metadata — the TTFT/ITL percentiles and the
per-kernel fallback/dispatch breakdown.  The deep-dive view is the same
file loaded in ``ui.perfetto.dev``; this is the no-browser triage pass.
"""

from __future__ import annotations

import argparse
from collections import Counter as TallyCounter
from typing import Dict, List

from repro.obs import MetricsRegistry, load_events, request_chains
from repro.obs.trace import COUNTER, INSTANT, PHASE_PREFIX, SPAN

INCIDENT_EVENTS = ("preempt", "retry_backoff", "tick_retry", "quarantine",
                   "poison", "cache_poisoned", "admission_error",
                   "cow_copy", "prefix_evict", "seize_pages",
                   "release_pages")


def _fmt_ms(summary: dict) -> str:
    return (f"n={summary['count']} "
            f"p50 {summary['p50'] * 1e3:.2f} / "
            f"p95 {summary['p95'] * 1e3:.2f} / "
            f"p99 {summary['p99'] * 1e3:.2f} / "
            f"max {summary['max'] * 1e3:.2f} ms")


def summarize_trace(events: List[tuple], meta: dict) -> List[str]:
    """The report lines (pure so tests can assert on content)."""
    lines: List[str] = []
    reg = MetricsRegistry()
    incidents: TallyCounter = TallyCounter()
    peaks: Dict[str, float] = {}
    for ev in events:
        kind, name = ev[0], ev[1]
        if kind == SPAN:
            reg.histogram(name).observe(ev[4])
        elif kind == COUNTER:
            peaks[name] = max(peaks.get(name, ev[4]), ev[4])
        elif kind == INSTANT and name in INCIDENT_EVENTS:
            incidents[name] += 1

    chains = request_chains(events)
    reasons = TallyCounter(c["finish"] for c in chains.values())
    n_tokens = sum(c["n_tokens"] for c in chains.values())
    lines.append(f"{len(events)} events, {len(chains)} requests, "
                 f"{n_tokens} tokens")
    if reasons:
        lines.append("finish reasons: " + ", ".join(
            f"{k or 'none'} {v}" for k, v in sorted(reasons.items(),
                                                    key=lambda p: str(p[0]))))
    for phase in ("queued", "prefill", "decode", "tick"):
        h = reg.histograms.get(phase)
        if h is not None and h.count:
            lines.append(f"{phase:>8}: {_fmt_ms(h.summary())}")
    for name, h in sorted(reg.histograms.items()):
        if name.startswith(PHASE_PREFIX):
            s = h.summary()
            lines.append(f"{name:>20}: n={s['count']} "
                         f"p50 {s['p50'] * 1e3:.3f} / "
                         f"p95 {s['p95'] * 1e3:.3f} ms, "
                         f"total {h.total:.3f} s")
    if peaks:
        lines.append("counter peaks: " + ", ".join(
            f"{k} {v:g}" for k, v in sorted(peaks.items())))
    if incidents:
        lines.append("incidents: " + ", ".join(
            f"{k} {v}" for k, v in sorted(incidents.items())))
    dropped = meta.get("dropped_events", 0)
    if dropped:
        lines.append(f"ring buffer dropped {dropped} events "
                     f"(oldest-first; raise Tracer(capacity=...))")

    metrics = meta.get("metrics") or {}
    for key, label in (("ttft", "TTFT"), ("itl", "ITL")):
        s = metrics.get(key)
        if s and s.get("count"):
            lines.append(f"{label:>8}: {_fmt_ms(s)}")
    fb = metrics.get("kernel_fallbacks_by_kernel") or {}
    if fb:
        lines.append("kernel fallbacks: " + ", ".join(
            f"{k} {v}" for k, v in sorted(fb.items())))
    disp = metrics.get("dispatch") or {}
    for section in ("resolves", "tune_hits", "tune_misses"):
        counts = disp.get(section) or {}
        if counts:
            lines.append(f"dispatch {section}: " + ", ".join(
                f"{k} {v}" for k, v in sorted(counts.items())))
    return lines


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="summarize a trace written by serve --trace-out")
    ap.add_argument("trace", help="path to a .json (Chrome-trace) or "
                                  ".jsonl export")
    args = ap.parse_args(argv)
    events, meta = load_events(args.trace)
    for line in summarize_trace(events, meta):
        print(line)


if __name__ == "__main__":
    main()
