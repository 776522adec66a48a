"""Training launcher: fault-tolerant driver around the sharded train step.

Runs real training at any scale the host provides:

  # CPU smoke run (1 device, reduced config, loss visibly decreases):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --steps 60 --batch 8 --seq 128

  # production mesh shapes are exercised via launch/dryrun.py; on a real
  # TPU fleet this same entry point runs with --mesh data,model=16,16.

Features wired here: synthetic shard-aware data (step-addressed),
AdamW + cosine/WSD schedule + global-norm clipping (all Goldschmidt-
routed), periodic async checkpointing, restart-on-failure, straggler
detection with elastic re-mesh, optional int8 EF gradient compression
across the 'pod' axis (multi-pod meshes).
"""

from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.store import config_fingerprint
from repro.data.synthetic import SyntheticLM
from repro.launch.jax_cache import use_persistent_cache
from repro.launch.mesh import auto_mesh
from repro.launch.steps import TrainHParams, make_train_step
from repro.optim import adamw_init
from repro.models import api
from repro.runtime import sharding as shr
from repro.runtime.driver import DriverConfig, TrainState, run_training
from repro.runtime.failures import FailureInjector, StragglerClock


def parse_mesh(spec: str):
    if not spec:
        return None
    names, sizes = spec.split("=")
    axes = tuple(names.split(","))
    shape = tuple(int(x) for x in sizes.split(","))
    return auto_mesh(shape, axes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="", help="e.g. data,model=16,16")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated chip failures at these steps")
    ap.add_argument("--straggle-from", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--kernel-impl", default=None, choices=("jnp", "pallas"),
                    help="override cfg.kernel_impl: 'pallas' trains through "
                         "the fused kernels (custom_vjp backward)")
    args = ap.parse_args()
    use_persistent_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.kernel_impl is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, kernel_impl=args.kernel_impl)
    mesh = parse_mesh(args.mesh)
    dp = shr.dp_axes(mesh, args.batch) if mesh else ()
    hp = TrainHParams(peak_lr=args.lr, warmup=min(20, args.steps // 4),
                      total=args.steps,
                      schedule="wsd" if cfg.name.startswith("minicpm") else "cosine")

    ds = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                     global_batch=args.batch, seed=args.seed)

    def init_state() -> TrainState:
        params = api.init(cfg, jax.random.key(args.seed))
        return TrainState(params, adamw_init(params), 0)

    def make_step_fn():
        fn = make_train_step(cfg, hp, mesh=mesh, dp=dp)
        if mesh is not None:
            psh = shr.tree_shardings(mesh, jax.eval_shape(
                lambda: api.init(cfg, jax.random.key(0))))
            osh = shr.tree_shardings(
                mesh, jax.eval_shape(lambda: adamw_init(
                    jax.eval_shape(lambda: api.init(cfg, jax.random.key(0))))))
            return jax.jit(fn, in_shardings=(psh, osh, None),
                           donate_argnums=(0, 1))
        return jax.jit(fn, donate_argnums=(0, 1))

    def make_batch(step: int):
        b = ds.global_batch_np(step)
        return {k: jnp.asarray(v) for k, v in b.items()}

    injector = FailureInjector(fail_at_steps=tuple(args.fail_at))
    clock = (StragglerClock(slow_from=args.straggle_from)
             if args.straggle_from is not None else None)

    stats = run_training(
        cfg=DriverConfig(total_steps=args.steps,
                         checkpoint_every=args.ckpt_every,
                         checkpoint_dir=args.ckpt_dir),
        init_state=init_state,
        make_step_fn=make_step_fn,
        make_batch=make_batch,
        fingerprint=config_fingerprint(cfg),
        injector=injector,
        clock=clock,
        log_every=args.log_every,
    )
    losses = stats["losses"]
    first = np.mean([losses[s] for s in sorted(losses)[:5]])
    last = np.mean([losses[s] for s in sorted(losses)[-5:]])
    print(f"done: steps={stats['state'].step} restarts={stats['restarts']} "
          f"remeshes={stats['remeshes']} loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
