#!/usr/bin/env python3
"""Compile a cell's prefill and decode tick for a described TPU v5e.

    JAX_PLATFORMS=cpu python bench/compile_v5e.py --workload internlm2-1.8b.chat

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, at the cell's shapes (its engine's
slots, cache length and page arena, and each prompt length its mix
sends).  Prints each program's ``memory_analysis`` (arguments, outputs,
temporaries, in bytes), so the slot and arena counts can be checked
against one chip's 16 GB before any chip time.  Nothing runs, so it
gives no time.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.serving import Engine, EngineConfig
    from repro.serving.cache import make_paged_cache

    from bench import harness, traffic_gen, weights

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    if cell.chips != 1:
        sys.exit("compile_v5e: one-chip cells only")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = harness.build_cfg(cell)
    arch = harness.arch_sizes(cfg)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                         sharding=one)
    params = jax.tree.map(sds, weights.abstract(
        arch, jnp.dtype(cfg.param_dtype)))
    ecfg = dict(cell.config.get("engine", {}))
    ecfg.update(cell.mix.get("engine", {}))
    eng = Engine(cfg, params, EngineConfig(**ecfg))
    n = eng.ecfg.n_slots
    report = {"workload": cell.name, "params_bytes": int(sum(
        np.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree.leaves(params)))}
    for s in traffic_gen.used_prompt_lengths(cell.mix, args.seconds):
        batch = {"tokens": jax.ShapeDtypeStruct((1, s), jnp.int32,
                                                sharding=one)}
        c = eng._prefill.lower(params, batch).compile()
        report[f"prefill_{s}"] = _mem(c)
        print(json.dumps({f"prefill_{s}": report[f"prefill_{s}"]}),
              flush=True)
    cache = jax.tree.map(sds, jax.eval_shape(lambda: make_paged_cache(
        cfg, n, eng._n_pages, eng.ecfg.page_size, jnp.dtype(cfg.dtype))))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=one)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
    tick = eng._tick_fn(False, 0, eng.ecfg.numeric_guard)
    c = tick.lower(params, cache, i32(n, eng._pages_per_slot), i32(n),
                   i32(n, 1), jax.ShapeDtypeStruct((n,), jnp.float32,
                                                   sharding=one),
                   i32(n), i32(n), key).compile()
    report["tick"] = _mem(c)
    report["arena_bytes"] = int(sum(np.prod(a.shape) * a.dtype.itemsize
                                    for a in jax.tree.leaves(cache)))
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
