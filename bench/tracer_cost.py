#!/usr/bin/env python3
"""What the engine's tracing costs with the profiler off, on the chip.

    python bench/tracer_cost.py --workload internlm2-1.8b.chat --seed 7 \
        --seconds 51 --order off,on,on,off

One set-up of the cell, then one window per entry of ``--order``, all on
the same seed (the same requests) and the same engine: ``on`` attaches
the ``repro.obs.Tracer`` that a traced run attaches (spans, counters and
host phases, each phase opening a profiler annotation that does nothing
while no profiler runs), ``off`` attaches none.  Prints one JSON line per
window: ``decode_tick_ms`` (host clock, as its reader computes it),
``itl_p95_ms`` and the events the tracer recorded.  No reference check:
``bench/run.py`` checks the same program.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--order", default="off,on,on,off")
    args = ap.parse_args()

    from bench import harness, traffic_gen
    from bench.stats import percentile

    cell = harness.load_cell(args.workload)
    st = harness.set_up(cell, args.seed, traffic_gen.used_prompt_lengths(
        cell.mix, args.seconds), trace=True)
    tracer, ecfg = st.tracer, st.engine.ecfg
    for mode in args.order.split(","):
        on = mode == "on"
        st.engine.ecfg = dataclasses.replace(ecfg,
                                             tracer=tracer if on else None)
        tracer.clear()
        win = harness.serve_window(st, cell, args.seed, args.seconds)
        m = win.serve
        print(json.dumps({
            "tracer": on, "seed": args.seed, "compiles": win.compiles,
            "decode_ticks": m.decode_ticks,
            "decode_tick_ms": m.decode_time_s / m.decode_ticks * 1e3,
            "itl_p95_ms": percentile(m.itl_samples, 95) * 1e3,
            "events": len(tracer.events)}), flush=True)


if __name__ == "__main__":
    main()
