"""Serving launcher: a thin CLI over the continuous-batching engine.

  # N identical requests through the slot pool (old lockstep shape):
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --smoke --batch 4 --prompt-len 32 --gen 32

  # Poisson-arrival trace with per-request prompt/gen lengths:
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --smoke --trace 12 --rate 40 --batch 4

  # Tensor-parallel over 8 (here: forced host) devices, 2-way data x
  # 4-way model:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --smoke --batch 4 --mesh 2x4

  # Paged KV cache: a shared page arena instead of per-slot max-length
  # rows, with prefix sharing (identical prompts prefill once):
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --smoke --trace 12 --pool paged --page-size 8 --pages 24

Requests are prefilled individually (one lowering per distinct prompt
length), grafted into the cache pool, and decoded by one fused jitted
tick over the whole pool with per-slot sequence positions — greedy or
temperature/top-k sampling through the Goldschmidt softmax runs inside
the jit.  ``--pool paged`` swaps the per-slot rows for the block-table
page arena (serving/cache.py) and prints its page/prefix stats —
admission reserves only the prompt's pages and appends pages as decode
crosses page boundaries (``--page-reserve worst`` restores the legacy
whole-budget reservation);
``--scheduler static`` degrades to the lockstep baseline for
comparison; ``benchmarks/bench_serve.py`` automates the comparisons
into ``BENCH_serve.json``.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.launch.jax_cache import use_persistent_cache
from repro.models import api
from repro.runtime import sharding as shr
from repro.serving import Engine, EngineConfig, Request, SamplingParams


def build_engine(cfg, ecfg: EngineConfig, *, seed: int = 0,
                 mesh=None) -> Engine:
    """The serving engine over seeded random weights.

    With a ``mesh`` the weights are initialized straight into the
    placement of the sharding rule table, so a model larger than one
    device never has to exist whole on the first one.
    """
    key = jax.random.key(seed)
    if mesh is None:
        params = api.init(cfg, key)
    else:
        init = lambda k: api.init(cfg, k)  # noqa: E731
        params = jax.jit(init, out_shardings=shr.tree_shardings(
            mesh, jax.eval_shape(init, key)))(key)
    return Engine(cfg, params, ecfg, mesh=mesh)


def build_requests(args, cfg, rng: np.random.RandomState):
    """Either --batch identical requests at t=0, or a Poisson trace."""
    frames = None
    if cfg.family == "encdec":
        frames = lambda: (rng.randn(cfg.enc_seq, cfg.d_model)  # noqa: E731
                          .astype(np.float32) * 0.1)
    if args.prompt_len < 1 or args.gen < 1:
        raise SystemExit("--prompt-len and --gen must be >= 1")
    if args.trace and args.rate <= 0:
        raise SystemExit("--rate must be > 0 (requests/second)")
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k,
                              deadline_ms=args.deadline_ms)
    if not args.trace:
        # genuinely identical: one prompt (and one frame draw) shared by
        # every request, so --pool paged demonstrates prefix sharing
        prompt = rng.randint(0, cfg.vocab, (args.prompt_len,))
        frame = frames() if frames else None
        return [
            Request(rid=i, prompt=prompt, max_new_tokens=args.gen,
                    sampling=sampling, frames=frame)
            for i in range(args.batch)]
    # Poisson arrivals at --rate req/s; prompt/gen drawn uniformly from
    # [len/2, len] so slots churn at different times.
    t = 0.0
    reqs = []
    for i in range(args.trace):
        t += float(rng.exponential(1.0 / args.rate))
        reqs.append(Request(
            rid=i,
            prompt=rng.randint(
                0, cfg.vocab,
                (int(rng.randint(max(1, args.prompt_len // 2),
                                 args.prompt_len + 1)),)),
            max_new_tokens=int(rng.randint(max(1, args.gen // 2),
                                           args.gen + 1)),
            sampling=sampling,
            arrival_time=t,
            frames=frames() if frames else None))
    return reqs


def report(outs, metrics, scheduler: str) -> None:
    ttfts = sorted(metrics.ttft_s.values())
    print(f"[{scheduler}] {metrics.n_requests} requests through "
          f"{metrics.n_slots} slots: "
          f"prefill {metrics.prefill_tokens} prompt tokens "
          f"(+{metrics.first_tokens} first tokens) in "
          f"{metrics.prefill_time_s * 1e3:.1f} ms")
    if metrics.decode_ticks:
        print(f"  decode: {metrics.decode_tokens} tokens in "
              f"{metrics.decode_ticks} ticks / "
              f"{metrics.decode_time_s * 1e3:.1f} ms "
              f"({metrics.decode_tok_per_s:.1f} tok/s, "
              f"occupancy {metrics.occupancy:.2f})")
    else:
        print("  decode: no steps (every request finished at prefill; "
              "gen budget 1)")
    if ttfts:
        t = metrics.ttft_summary
        print(f"  TTFT ms: min {t['min'] * 1e3:.1f} / "
              f"p50 {t['p50'] * 1e3:.1f} / p95 {t['p95'] * 1e3:.1f} / "
              f"p99 {t['p99'] * 1e3:.1f} / max {t['max'] * 1e3:.1f}")
    if metrics.itl_samples:
        i = metrics.itl_summary
        print(f"  ITL ms ({i['count']} samples): "
              f"p50 {i['p50'] * 1e3:.1f} / p95 {i['p95'] * 1e3:.1f} / "
              f"p99 {i['p99'] * 1e3:.1f}")
    pool = metrics.pool
    if pool.get("kind") == "paged":
        print(f"  pages: {pool['peak_pages_in_use']}/{pool['n_pages']} peak "
              f"in use (page_size {pool['page_size']}), "
              f"prefix hits {pool['prefix_hits']} "
              f"({pool['prefix_hit_tokens']} prompt tokens shared, "
              f"{metrics.prefill_skips} prefills skipped), "
              f"cow copies {pool['cow_copies']}, "
              f"cache bytes {pool['cache_bytes']}")
        print(f"  reservation ({pool['reserve']}): "
              f"{pool['written_pages']}/{pool['reserved_pages']} "
              f"reserved pages written, "
              f"{pool['appended_pages']} appended mid-decode, "
              f"resume hits {pool['resume_hits']} "
              f"({pool['resume_tokens']} prompt tokens resumed)")
    fails = dict(failed=metrics.failed, cancelled=metrics.cancelled,
                 timed_out=metrics.timed_out, preempted=metrics.preempted,
                 retried=metrics.retried,
                 kernel_fallbacks=metrics.kernel_fallbacks)
    if any(fails.values()):
        print("  failures: " + ", ".join(
            f"{k} {v}" for k, v in fails.items() if v))
    else:
        print("  failures: none")
    if metrics.kernel_fallbacks_by_kernel:
        print("  kernel fallbacks: " + ", ".join(
            f"{k} {v}" for k, v in
            sorted(metrics.kernel_fallbacks_by_kernel.items())))
    print("sample generations (token ids):")
    for rid in sorted(outs)[:4]:
        print(f"  req {rid}:", outs[rid].tokens[:24].tolist())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="slot-pool width; without --trace, also the "
                         "number of requests")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0, metavar="N",
                    help="serve N Poisson-arrival requests with varied "
                         "prompt/gen lengths instead of a uniform batch")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="--trace arrival rate, requests/second")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples via the Goldschmidt "
                         "softmax")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency bound from arrival; an "
                         "expired request finishes with reason "
                         "'deadline' (partial tokens kept)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="retry budget for admission-queue overflow and "
                         "transient tick failures")
    ap.add_argument("--scheduler", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--pool", choices=("slot", "paged"), default="slot",
                    help="decode-cache layout: per-slot max-length rows "
                         "or the block-table page arena with prefix "
                         "sharing")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--pool paged: tokens per arena page")
    ap.add_argument("--pages", type=int, default=0,
                    help="--pool paged: arena pages (0 = worst case; "
                         "size it down to actually save memory)")
    ap.add_argument("--page-reserve", choices=("prompt", "worst"),
                    default="prompt",
                    help="--pool paged admission footprint: 'prompt' "
                         "reserves only the prompt's pages and appends "
                         "pages as decode crosses page boundaries; "
                         "'worst' keeps the legacy whole-budget "
                         "reservation (prompt+gen) at admission")
    ap.add_argument("--quant", choices=("none", "int8"), default="none",
                    help="int8: quantize weights per-tensor and the KV "
                         "arena on the static KV scale; division sites "
                         "route through the fixed-point Goldschmidt "
                         "datapath under kernel_impl='pallas'")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="serve sharded over a (data, model) device mesh: "
                         "'DxM', 'data=D,model=M', a bare TP width 'M', "
                         "or 'auto' (TP over every device); default: "
                         "single-device engine")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the request-lifecycle trace and write it "
                         "here: '.jsonl' = line-delimited event log, "
                         "anything else = Chrome-trace JSON loadable in "
                         "ui.perfetto.dev")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the pre-compile pass; reported TTFT then "
                         "includes one-time jit compilation")
    ap.add_argument("--autotune", action="store_true",
                    help="pre-tune kernel configs for this serving shape "
                         "(persists to the tuning cache) and serve with "
                         "tuned dispatch enabled")
    args = ap.parse_args()
    use_persistent_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.quant != "none":
        import dataclasses

        cfg = dataclasses.replace(cfg, quant=args.quant)
    s_max = args.prompt_len + args.gen
    assert s_max <= cfg.max_seq, (s_max, cfg.max_seq)

    if args.autotune:
        import dataclasses

        from repro.kernels import tuning

        tuning.enable_tuning(True)
        # Serve through the Pallas kernels: the jnp path has no tunable
        # launch config, so tuned dispatch only means something here.
        cfg = dataclasses.replace(cfg, kernel_impl="pallas")
        for res in tuning.autotune_for_model(
                d_model=cfg.d_model, n_heads=cfg.n_heads,
                head_dim=cfg.head_dim_, batch=args.batch,
                prompt_len=args.prompt_len):
            src = ("cache hit" if res.from_cache
                   else f"timed {len(res.trials)} candidates")
            print(f"autotune {res.kernel}: {res.config} "
                  f"({src}, {res.us_per_call:.0f} us/call)")
        print(f"tuning cache: {tuning.cache_path()}")

    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
        print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} "
              f"{mesh.devices.flat[0].platform} devices")

    tracer = None
    if args.trace_out:
        from repro.obs import Tracer

        tracer = Tracer()
    rng = np.random.RandomState(args.seed)
    engine = build_engine(cfg, EngineConfig(
        n_slots=args.batch, s_max=s_max, seed=args.seed, pool=args.pool,
        page_size=args.page_size, n_pages=args.pages,
        page_reserve=args.page_reserve,
        max_retries=args.max_retries, tracer=tracer),
        seed=args.seed, mesh=mesh)
    reqs = build_requests(args, cfg, rng)
    if not args.no_warmup:
        # compile prefill (per distinct length) + the tick up front so the
        # reported TTFT/tok-s measure serving, not one-time XLA lowering
        engine.warmup(sorted({r.prompt_len for r in reqs}),
                      stochastic=args.temperature > 0)
        if tracer is not None:
            tracer.clear()  # warmup spans are compilation, not serving
    outs, metrics = engine.run(reqs, scheduler=args.scheduler)
    report(outs, metrics, args.scheduler)
    if tracer is not None:
        from repro.obs import write_chrome_trace, write_jsonl

        meta = {"arch": args.arch, "scheduler": args.scheduler,
                "metrics": metrics.to_dict()}
        writer = (write_jsonl if args.trace_out.endswith(".jsonl")
                  else write_chrome_trace)
        writer(args.trace_out, tracer, metadata=meta)
        print(f"trace: {len(tracer)} events -> {args.trace_out} "
              f"(dropped {tracer.dropped}); view with "
              f"'python -m repro.launch.obsview {args.trace_out}'")


if __name__ == "__main__":
    main()
