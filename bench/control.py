#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python bench/control.py --workload internlm2-1.8b.chat \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds 20 \
        [--fault half_batch --fault-seeds 4,5,6]

For each seed of ``--seeds`` the program runs the cell as it is timed
(its configuration, engine and mix, a window of ``--seconds``) and the
widest reference gap of the served tokens is read, as a benchmark run
reads it.  For each of ``--control-seeds`` the same is read from the
control: the program with its own int8 path switched on (int8 weights
and KV cache, fixed-point Goldschmidt), the precision below the
bfloat16 the configuration computes in.  For each of ``--fault-seeds``
it is read from the program with a fault of ``bench/faults.py``
planted.  One JSON line per reading; the limit in
``bench/limits/<cell>.json`` lies between the largest sound reading and
the smallest control reading.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

CONTROL = {"quant": "int8"}


def reading(cell, seed: int, seconds: float, cfg_extra=None, *,
            require_tpu: bool = True, kind: str = "program") -> dict:
    """One window of the cell and the widest gap of what it served."""
    from bench import check, harness, traffic_gen

    st = harness.set_up(cell, seed, traffic_gen.used_prompt_lengths(
        cell.mix, seconds), require_tpu=require_tpu, cfg_extra=cfg_extra)
    win = harness.serve_window(st, cell, seed, seconds)
    st.engine = None
    gc.collect()
    t0 = time.perf_counter()
    v = check.compare(st.params, cell, win.requests, win.outs, seed)
    check_s = time.perf_counter() - t0
    gaps = v["gaps"]
    return {"seed": seed, "kind": kind,
            "max_logit_gap": v["checks"]["max_logit_gap"]["value"],
            "per_request_max": {str(r): float(g.max())
                                for r, g in gaps.items()},
            "tokens": v["checks"]["max_logit_gap"]["tokens"],
            "requests": v["checks"]["max_logit_gap"]["requests"],
            "disagree_share": (sum(int((g > 0).sum()) for g in gaps.values())
                               / max(1, sum(len(g) for g in gaps.values()))),
            "sent": len(win.requests), "window_s": win.window_s,
            "check_s": check_s}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", default="", help="a fault of bench/faults.py")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()
    from bench import faults, harness

    cell = harness.load_cell(args.workload)

    def seeds(text):
        return [int(x) for x in text.split(",") if x]

    for s in seeds(args.seeds):
        print(json.dumps(reading(cell, s, args.seconds)), flush=True)
    for s in seeds(args.control_seeds):
        print(json.dumps(reading(cell, s, args.seconds, CONTROL,
                                 kind="int8")), flush=True)
    for s in seeds(args.fault_seeds):
        with faults.FAULTS[args.fault]():
            print(json.dumps(reading(cell, s, args.seconds,
                                     kind=args.fault)), flush=True)


if __name__ == "__main__":
    main()
