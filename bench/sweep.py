#!/usr/bin/env python3
"""Offered-load sweep of an open-loop cell, to find its knee once.

    python bench/sweep.py --workload internlm2-1.8b.chat --seed 1 \
        --seconds 30 --rates 1.5,2,2.5,3

One set-up, then one window per rate (the cell's mix with only
``rate_per_s`` changed).  Prints, per rate, the requests sent and
failed, TTFT p50/p90, ITL p95, output tokens per second and the
engine's decode tick; the knee is the highest rate whose TTFT tail stays
flat and whose output rate still follows the offered load.  Not part of
a benchmark run.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args()
    from bench import check, harness, stats, traffic_gen

    cell = harness.load_cell(args.workload)
    st = harness.set_up(cell, args.seed, traffic_gen.used_prompt_lengths(
        cell.mix, args.seconds))
    print(f"set-up {time.perf_counter() - T_PROCESS:.1f} s", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = json.loads(json.dumps(cell.mix))
        mix["arrivals"]["rate_per_s"] = rate
        c = dataclasses.replace(cell, mix=mix)
        win = harness.serve_window(st, c, args.seed, args.seconds)
        rec = harness.Record(arch=st.arch, chips=c.chips, peaks={},
                             outs=win.outs, serve=win.serve,
                             window_s=win.window_s, setup_s=0.0)
        ttft = stats.ttft_samples(rec)
        m = win.serve
        print(json.dumps({
            "rate_per_s": rate, "sent": len(win.requests),
            "failed": sum(check.is_failure(c, o) for o in win.outs.values()),
            "ttft_p50_s": stats.percentile(ttft, 50),
            "ttft_p90_s": stats.percentile(ttft, 90),
            "itl_p95_ms": stats.percentile(m.itl_samples, 95) * 1e3,
            "output_tok_s": stats.tokens_out(rec) / win.window_s,
            "decode_tick_ms": m.decode_time_s / max(m.decode_ticks, 1) * 1e3,
            "occupancy": m.occupancy, "preempted": m.preempted,
            "window_s": win.window_s, "compiles": win.compiles}),
            flush=True)


if __name__ == "__main__":
    main()
