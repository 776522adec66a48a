#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) this machine holds.

    python bench/run.py --workload internlm2-1.8b.chat --seed 7 \
        --seconds 51 --trace 0

Set-up (weights from the seed, the engine, a warm-up of every prompt
length the mix sends and of the decode tick) counts into ``setup_s``;
then one ``Engine.run`` serves the mix's whole schedule, and the
reference check compares a sample of what it served.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device`` and, traced, ``breakdown``; its last key,
``checks``, gives each compared number beside its limit, and the last
lines of standard error repeat them.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
Compilations are cached in ``JAX_COMPILATION_CACHE_DIR`` when it is set,
else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the arrival window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("bench: the program (src/repro) is not in this checkout")
    from bench import harness

    cell = harness.load_cell(args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
