#!/usr/bin/env python3
"""Run the serving path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: internlm2-1.8b
    python chip_smoke.py --chips 4   # four chips: granite-3-8b on a 1x4 mesh

One chip (the default).  The Goldschmidt Pallas kernels are first checked
against their error bounds on the chip.  Then internlm2-1.8b, at its
published widths with all 24 layers and seeded random weights, is served
through the Pallas kernels by the engine ``repro.launch.serve`` builds:
paged pool, exact prefix sharing, 8 slots, 8 greedy requests with
prompts of 512 and 1024 tokens and 64 new tokens each.  The run fails
unless every request finishes by length, no kernel fell back to jnp, the
prefill step lowers to compiled Mosaic kernels (``tpu_custom_call``), and
the Pallas prefill logits agree with the exact jnp model.

Four chips (``--chips 4``, and only that phase).  granite-3-8b cut to 4
layers runs in float32 sharded over a 1x4 serving mesh and unsharded on
device 0; prefill and 16 decode steps' logits must agree.  Then the full
40-layer granite-3-8b, whose float32 weights do not fit one chip, answers
4 requests on the mesh.

The lines before the last report one smoke run; they are not benchmark
numbers.  The last line, printed only when every check passed, is one
JSON object: ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero and prints no result.  Everything runs in this process,
which holds the chip; the script starts no other.  Compilations are
cached in ``JAX_COMPILATION_CACHE_DIR`` when it is set, else in
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

# Agreement bounds, relative L2 error of the last-position logits:
# ||got - want|| / ||want||.
#
# One chip, Pallas path vs the exact jnp model.  Both run bf16 activations
# with float32 weights.  The Pallas path divides through the seed-only
# bf16 Goldschmidt datapath (8-bit ROM, no refinement pass: relative error
# up to 2^-8 per rmsnorm scale and attention denominator), multiplies the
# float32 weights at the chip's default one-pass bf16 precision, and
# accumulates attention blockwise; the reference divides exactly at the
# highest matmul precision.  Each of these is a ~2^-8 relative
# perturbation that the 24-layer residual stream carries to the logits.
PALLAS_VS_EXACT_REL_L2 = 5e-2
# Four chips, sharded vs unsharded, both float32 at the highest matmul
# precision: only the order of the partial sums differs (float32
# rounding, ~2^-24 per add), so the bound sits far below any real fault.
SHARDED_VS_SINGLE_REL_L2 = 1e-4

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Checks:
    """Prints each check; a failed one fails the run at its end, after
    the later phases have reported too."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"check {what}: {'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            self.failed.append(what)


def tpu_devices(n: int):
    """The chips, or exit: there is no CPU branch."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


class CompileLog:
    """Counts XLA compilations (persistent-cache loads included) and
    their seconds, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.n += 1
            self.secs += secs

    def mark(self):
        return self.n, self.secs

    def since(self, mark):
        return self.n - mark[0], self.secs - mark[1]


def rel_l2(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def kernel_phase(check: Checks) -> None:
    """Every float kernel on the chip against its exact oracle and error
    bound, plus the ROM read itself: a reciprocal with no refinement pass
    is the seed word times a power of two, so it must equal the jnp
    oracle's gathered seed bit for bit."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import bench_kernels
    from repro.kernels import ops, ref

    for name, args, pallas, _, err in bench_kernels.bench_cases(smoke=False):
        dev = [jnp.asarray(a) for a in args]
        e = float(err(np.asarray(pallas(*dev)), args))
        bound = bench_kernels.ERR_BOUNDS[(name, "float32")]
        print(f"kernel {name}: max err {e!r} (bound {bound!r})")
        check(e <= bound, f"{name} within its float32 error bound")
    x = jnp.asarray(np.exp(np.random.RandomState(7).uniform(
        -3, 3, (256, 2048))).astype(np.float32))
    for name, pallas, oracle in (("gs_recip", ops.gs_recip, ref.reciprocal),
                                 ("gs_rsqrt", ops.gs_rsqrt, ref.rsqrt)):
        got = np.asarray(pallas(x, p=8, iters=0))
        want = np.asarray(oracle(x, p=8, iters=0))
        print(f"kernel {name} seed: {int(np.sum(got != want))} of {got.size}"
              f" words differ from the jnp oracle")
        check(np.array_equal(got, want),
              f"{name} ROM seed bit-identical to the jnp oracle")


def one_chip(seed: int, check: Checks):
    import jax
    import numpy as np

    from repro import configs
    from repro.launch.jax_cache import use_persistent_cache
    from repro.launch.serve import build_engine
    from repro.launch.steps import make_prefill_step
    from repro.serving import EngineConfig, Request
    from repro.serving.engine import prefill_batch
    from repro.serving.requests import FINISH_LENGTH

    (dev,) = tpu_devices(1)
    print(f"device: {dev.platform} {dev.device_kind} (smoke run, not a "
          f"benchmark)")
    print(f"compile cache: {use_persistent_cache()}")
    log = CompileLog()

    mark = log.mark()
    kernel_phase(check)
    n_comp, comp_s = log.since(mark)
    print(f"kernel phase: {n_comp} compilations, {comp_s!r} compile seconds")

    cfg = dataclasses.replace(configs.get_config("internlm2-1.8b"),
                              kernel_impl="pallas")
    lens, gen = (512, 1024), 64
    engine = build_engine(cfg, EngineConfig(
        n_slots=8, s_max=max(lens) + gen, seed=seed, pool="paged",
        prefix="exact"), seed=seed)
    rng = np.random.RandomState(seed)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab,
                                              (lens[i % 2],)),
                    max_new_tokens=gen) for i in range(8)]

    mark = log.mark()
    t0 = time.perf_counter()
    engine.warmup(sorted(set(lens)))
    n_comp, comp_s = log.since(mark)
    print(f"warmup: {time.perf_counter() - t0!r} s wall, {n_comp} "
          f"compilations, {comp_s!r} compile seconds")

    mark = log.mark()
    outs, m = engine.run(reqs)
    n_run, _ = log.since(mark)
    print(f"serve: {len(outs)} requests, TTFT p50 "
          f"{m.ttft_summary['p50']!r} s, decode {m.decode_tok_per_s!r} "
          f"tok/s, {m.decode_ticks} ticks, {n_run} compilations in the run")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(f"kernel_fallbacks {m.kernel_fallbacks}")
    reasons = sorted({o.finish_reason for o in outs.values()})
    print(f"finish reasons: {reasons}")
    check(len(outs) == len(reqs)
          and all(o.finish_reason == FINISH_LENGTH
                  and len(o.tokens) == gen for o in outs.values()),
          "every request finished by length")
    check(m.kernel_fallbacks == 0, "no kernel fell back to jnp")

    prefill = jax.jit(make_prefill_step(cfg))
    lowered = prefill.lower(engine.params, prefill_batch(cfg, reqs[0]))
    n_custom = lowered.as_text().count("tpu_custom_call")
    print(f"prefill step: {n_custom} tpu_custom_call sites")
    check(n_custom > 0, "prefill runs compiled Pallas kernels")

    mark = log.mark()
    exact = jax.jit(make_prefill_step(dataclasses.replace(
        cfg, kernel_impl="jnp", policy_mode="exact")))
    for req in reqs[:4:2]:  # two prompts of one length: one more compile
        batch = prefill_batch(cfg, req)
        got = prefill(engine.params, batch)[0]
        with jax.default_matmul_precision("highest"):
            want = exact(engine.params, batch)[0]
        err = rel_l2(got, want)
        print(f"prefill logits, prompt {req.rid} ({req.prompt_len} tokens): "
              f"rel L2 {err!r} vs exact jnp (bound "
              f"{PALLAS_VS_EXACT_REL_L2!r})")
        check(err <= PALLAS_VS_EXACT_REL_L2,
              f"prompt {req.rid} Pallas logits agree with exact jnp")
    n_comp, comp_s = log.since(mark)
    print(f"logit comparison: {n_comp} compilations, {comp_s!r} compile "
          f"seconds")
    return dev, 1, log


def _prefill_decode_logits(cfg, params, prompt, n_steps, s_max, *,
                           mesh=None, tokens=None):
    """Last-position logits of the prefill and of ``n_steps`` decode
    steps, and the tokens fed: greedy from the run's own logits, or
    ``tokens`` (teacher-forced, so two runs see one input)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.serving import Request
    from repro.serving.cache import SlotCachePool
    from repro.serving.engine import prefill_batch

    req = Request(rid=0, prompt=prompt, max_new_tokens=n_steps + 1)
    prefill = jax.jit(make_prefill_step(cfg, mesh=mesh))
    decode = jax.jit(make_decode_step(cfg, mesh=mesh), donate_argnums=(1,))
    grow = jax.jit(lambda st: SlotCachePool.grow(
        cfg, st, 1, s_max, jnp.dtype(cfg.dtype)))
    logits, states, _ = prefill(params, prefill_batch(cfg, req))
    cache = grow(states)
    out, fed = [logits[0, -1]], []
    for i in range(n_steps):
        fed.append(int(jnp.argmax(out[-1])) if tokens is None
                   else tokens[i])
        logits, cache = decode(params, cache, jnp.int32(len(prompt) + i),
                               {"token": jnp.asarray([[fed[-1]]],
                                                     jnp.int32)})
        out.append(logits[0, -1])
    return jax.device_get(out), fed


def four_chips(seed: int, check: Checks):
    import jax
    import numpy as np

    from repro import configs
    from repro.launch.jax_cache import use_persistent_cache
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import build_engine
    from repro.models import api
    from repro.runtime import sharding as shr
    from repro.serving import EngineConfig, Request
    from repro.serving.requests import FINISH_LENGTH

    devs = tpu_devices(4)
    print(f"devices: {len(devs)} x {devs[0].platform} "
          f"{devs[0].device_kind} (smoke run, not a benchmark)")
    print(f"compile cache: {use_persistent_cache()}")
    log = CompileLog()
    mesh = make_serving_mesh("1x4")
    rng = np.random.RandomState(seed)

    # sharded vs unsharded, same process, float32 at the highest precision
    cut = configs.get_config("granite-3-8b", n_layers=4, dtype="float32")
    prompt = rng.randint(0, cut.vocab, (128,))
    n_dec = 16
    with jax.default_matmul_precision("highest"):
        params = jax.device_put(api.init(cut, jax.random.key(seed)), devs[0])
        single, toks = _prefill_decode_logits(cut, params, prompt, n_dec,
                                              256)
        sharded_params = jax.device_put(params, shr.tree_shardings(
            mesh, jax.eval_shape(lambda: params)))
        del params
        sharded, _ = _prefill_decode_logits(cut, sharded_params, prompt,
                                            n_dec, 256, mesh=mesh,
                                            tokens=toks)
        del sharded_params
    errs = [rel_l2(a, b) for a, b in zip(sharded, single)]
    print(f"4-layer granite, prefill + {n_dec} decode steps: max rel L2 "
          f"{max(errs)!r} sharded vs unsharded (bound "
          f"{SHARDED_VS_SINGLE_REL_L2!r})")
    check(max(errs) <= SHARDED_VS_SINGLE_REL_L2,
          "sharded logits agree with unsharded")

    cfg = configs.get_config("granite-3-8b")
    plen, gen = 256, 32
    engine = build_engine(cfg, EngineConfig(
        n_slots=4, s_max=plen + gen, seed=seed, pool="paged",
        prefix="exact"), seed=seed, mesh=mesh)
    split = sum(not x.sharding.is_fully_replicated
                for x in jax.tree.leaves(engine.params))
    in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
    print(f"40-layer granite params: {api.param_count(cfg)} parameters, "
          f"{split}/{len(jax.tree.leaves(engine.params))} leaves partitioned"
          f" (the rest replicated); "
          f"bytes_in_use per device {in_use}")
    check(max(in_use) < 2 * min(in_use), "params spread over the four chips")
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (plen,)),
                    max_new_tokens=gen) for i in range(4)]
    engine.warmup([plen])
    outs, m = engine.run(reqs)
    peak = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    print(f"serve: {len(outs)} requests, TTFT p50 {m.ttft_summary['p50']!r}"
          f" s, decode {m.decode_tok_per_s!r} tok/s; peak_bytes_in_use per "
          f"device {peak}")
    check(len(outs) == len(reqs)
          and all(o.finish_reason == FINISH_LENGTH
                  and len(o.tokens) == gen for o in outs.values()),
          "every request finished by length")
    return devs[0], len(devs), log


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving smoke; 4: the sharded "
                         "granite-3-8b phase only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()
    check = Checks()
    dev, count, log = (four_chips if args.chips == 4
                       else one_chip)(args.seed, check)
    print(f"compile total: {log.n} compilations, {log.secs!r} compile "
          f"seconds")
    if check.failed:
        sys.exit(f"chip_smoke: {len(check.failed)} checks failed: "
                 f"{'; '.join(check.failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
