"""Benchmark entry point: one section per paper table/claim + the
framework roofline summary.  Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # all sections
  PYTHONPATH=src python -m benchmarks.run --only cycles

``--smoke`` runs the kernel sweep only (1 timing repeat) and writes the
structured per-kernel records — µs/call + max-err, pallas vs jnp, the
fixed seed (7, 2) literals vs the dtype-derived precision policy — to
``BENCH_kernels.json`` (override with ``--json PATH``).  ``--check``
exits non-zero if any kernel's max error exceeds its dtype bound (the
CI bench-smoke gate).

``--serve`` runs the continuous-vs-static serving benchmark instead and
writes ``BENCH_serve.json``; with ``--check`` it exits non-zero on a
parity or occupancy regression (the CI serve-smoke gate).
"""

from __future__ import annotations

import argparse
import json
import sys

SECTIONS = ("cycles", "accuracy", "divider", "kernels", "roofline")
DEFAULT_JSON = "BENCH_kernels.json"
DEFAULT_SERVE_JSON = "BENCH_serve.json"


def _kernel_records(smoke: bool, json_path: str) -> list:
    from benchmarks import bench_kernels

    recs = bench_kernels.records(smoke=smoke)
    with open(json_path, "w") as f:
        json.dump({"smoke": smoke, "rows": recs}, f, indent=2)
    for r in recs:
        cfg = r["config"]
        pi = f"p={cfg['p']}/i={cfg['iters']}" if cfg else "-"
        print(f"{r['kernel']},{r['us_per_call']},"
              f"\"{r['dtype']} {r['impl']} {r['policy']} {pi} "
              f"err={r['max_err']:.2e} bound={r['err_bound']:.2e} "
              f"ok={r['ok']}\"")
        sys.stdout.flush()
    print(f"# wrote {len(recs)} records to {json_path}", file=sys.stderr)
    return recs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=SECTIONS, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="kernel records only, 1 timing repeat, write JSON")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help=f"write kernel records here (default {DEFAULT_JSON} "
                         "when --smoke/--check)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if any kernel max-err exceeds its dtype "
                         "bound")
    ap.add_argument("--serve", action="store_true",
                    help="run the continuous-vs-static serving benchmark "
                         f"only, write {DEFAULT_SERVE_JSON}; with --check, "
                         "fail on parity/occupancy regressions")
    ap.add_argument("--serve-mesh", default=None, metavar="SPEC",
                    help="run the --serve trace through the tensor-"
                         "parallel engine on this (data, model) mesh "
                         "(e.g. 2x4; the BENCH_serve.json n_devices "
                         "dimension)")
    args = ap.parse_args()
    if args.serve_mesh and not args.serve:
        ap.error("--serve-mesh requires --serve")
    from repro.launch.jax_cache import use_persistent_cache

    use_persistent_cache()

    print("name,us_per_call,derived")
    if args.serve:
        from benchmarks import bench_serve

        # always the smoke shapes: the full-config trace is a TPU job,
        # not a CI/CPU one (run bench_serve.serve_records(smoke=False)
        # directly for it)
        rec = bench_serve.serve_records(
            smoke=True, json_path=args.json or DEFAULT_SERVE_JSON,
            mesh_spec=args.serve_mesh)
        m_c, m_s = rec["continuous"], rec["static"]
        for sched, m in (("continuous", m_c), ("static", m_s)):
            print(f"serve_{sched},{m['decode_time_s'] * 1e6 / max(m['decode_ticks'], 1):.1f},"
                  f"\"n_devices={rec['n_devices']} "
                  f"{m['decode_tokens']} tok / {m['decode_ticks']} ticks, "
                  f"{m['aggregate_tok_per_s']:.1f} tok/s aggregate, "
                  f"occupancy {m['occupancy']:.2f}\"")
        print(f"serve_speedup,0,\"ticks x{rec['tick_speedup']:.2f} "
              f"tok/s x{rec['tok_s_speedup']:.2f} "
              f"(normalized x{rec['tok_s_speedup_normalized']:.2f}) "
              f"checks={rec['checks']}\"")
        pm, pool = rec["paged"], rec["paged"]["pool"]
        print(f"serve_paged,{pm['decode_time_s'] * 1e6 / max(pm['decode_ticks'], 1):.1f},"
              f"\"pages {pool['peak_pages_in_use']}/{pool['n_pages']} peak "
              f"(page_size {pool['page_size']}), "
              f"bytes x{rec['paged_bytes_ratio']:.3f} vs slot pool, "
              f"cow {pool['cow_copies']}, evictions {pool['evictions']}\"")
        px = rec["prefix"]
        print(f"serve_prefix,0,\"shared prompt x8: "
              f"{px['prefill_skips']} prefills skipped, "
              f"{px['prefix_hit_tokens']} prompt tokens shared, "
              f"prefill_tokens {px['prefill_tokens']}\"")
        pa = rec["paged_append"]
        print(f"serve_paged_append,0,\"written/reserved "
              f"x{pa['utilization']:.2f} (worst "
              f"x{pa['worst_utilization']:.2f}), peak_active "
              f"{pa['peak_active_append']} vs {pa['peak_active_worst']} "
              f"worst-case, resume prefill "
              f"{pa['resume']['sharer_prefill_tokens']}/"
              f"{pa['resume']['cold_prefill_tokens']} tokens "
              f"(x{pa['resume']['compute_ratio']:.2f})\"")
        qt = rec["quant"]
        qm = qt["slot"]
        print(f"serve_quant,{qm['decode_time_s'] * 1e6 / max(qm['decode_ticks'], 1):.1f},"
              f"\"int8 params x{qt['param_bytes_int8'] / max(qt['param_bytes_fp32'], 1):.3f} vs fp32, "
              f"bytes x{qt['bytes_ratio_vs_bf16']:.3f} vs bf16, "
              f"matched {qt['matched_frac_vs_fp32']:.2f} vs fp32 ref, "
              f"pools agree={qt['pool_parity']}\"")
        rs = rec["resilience"]
        print(f"serve_resilience,{rs['tick_us_guard_on']:.1f},"
              f"\"numeric guard x{rs['overhead_ratio']:.3f} per tick "
              f"(off: {rs['tick_us_guard_off']:.1f} us, "
              f"budget x{rs['budget']:.2f})\"")
        lat = rec["latency"]["continuous"]
        print(f"serve_latency,{lat['ttft']['p50'] * 1e6:.1f},"
              f"\"continuous TTFT ms p50/p95/p99 "
              f"{lat['ttft']['p50'] * 1e3:.2f}/"
              f"{lat['ttft']['p95'] * 1e3:.2f}/"
              f"{lat['ttft']['p99'] * 1e3:.2f}, "
              f"ITL {lat['itl']['p50'] * 1e3:.2f}/"
              f"{lat['itl']['p95'] * 1e3:.2f}/"
              f"{lat['itl']['p99'] * 1e3:.2f} "
              f"({lat['itl']['count']} samples)\"")
        ob = rec["obs"]
        print(f"serve_obs,{ob['tick_us_traced']:.1f},"
              f"\"tracing x{ob['overhead_ratio']:.3f} per tick "
              f"(off: {ob['tick_us_plain']:.1f} us, "
              f"budget x{ob['budget']:.2f}), "
              f"{ob['events']} events, "
              f"chain_problems={len(ob['chain_problems'])}, "
              f"export_problems={len(ob['export_problems'])}\"")
        print(f"# wrote {args.json or DEFAULT_SERVE_JSON}", file=sys.stderr)
        if args.check and not rec["ok"]:
            for name, ok in rec["checks"].items():
                if not ok:
                    print(f"# REGRESSION serve: {name} failed",
                          file=sys.stderr)
            sys.exit(1)
        return
    # The records flags act on the kernel sweep; an --only for a different
    # section means there are no kernel records to write or gate.
    records_mode = (args.smoke or args.json or args.check) and (
        args.only in (None, "kernels"))
    if records_mode:
        recs = _kernel_records(args.smoke,
                               args.json or DEFAULT_JSON)
        if args.check:
            bad = [r for r in recs if not r["ok"]]
            for r in bad:
                print(f"# REGRESSION {r['kernel']} {r['dtype']} "
                      f"{r['impl']}/{r['policy']}: max_err={r['max_err']:.2e}"
                      f" > bound={r['err_bound']:.2e}", file=sys.stderr)
            if bad:
                sys.exit(1)
        if args.smoke:
            return

    for section in SECTIONS:
        if args.only and section != args.only:
            continue
        if section == "kernels" and records_mode:
            continue  # the records sweep above supersedes this section
        if section == "cycles":
            from benchmarks import bench_cycles as mod
        elif section == "accuracy":
            from benchmarks import bench_accuracy as mod
        elif section == "divider":
            from benchmarks import bench_divider as mod
        elif section == "kernels":
            from benchmarks import bench_kernels as mod
        else:
            from benchmarks import roofline as mod
        for r in mod.rows():
            print(f"{r['name']},{r['us_per_call']},\"{r['derived']}\"")
            sys.stdout.flush()


if __name__ == "__main__":
    main()
