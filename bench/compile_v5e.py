#!/usr/bin/env python3
"""Compile a cell's prefill and decode tick for a described TPU v5e.

    JAX_PLATFORMS=cpu python bench/compile_v5e.py --workload internlm2-1.8b.chat

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, at the cell's shapes (its engine's
slots, cache length and page arena, and each prompt length its mix
sends).  A one-chip cell compiles for one chip of it; a cell of
``chips`` 4 for all four, on the mesh its configuration names, built by
``make_serving_mesh`` and sharded as ``harness.set_up`` shards the
weights and the engine the pool.  Prints each program's
``memory_analysis`` (arguments, outputs, temporaries) and the bytes of
the weights and the page arena, all in bytes on one device, so the slot
and arena counts can be checked against one chip's 16 GB before any
chip time.  Nothing runs, so it gives no time.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}


def _device_bytes(tree) -> int:
    """Bytes of ``tree`` (shapes with shardings) on its fullest device."""
    import numpy as np

    return int(sum(np.prod(a.sharding.shard_shape(a.shape))
                   * a.dtype.itemsize for a in tree))


def described_mesh(spec, devices):
    """The mesh ``make_serving_mesh(spec)`` builds on a host that holds
    ``devices``, over those (described) devices: the program's own spec
    grammar and axis types, with JAX's device count and default devices
    the described ones while it runs."""
    import jax

    from repro.launch.mesh import make_serving_mesh

    with mock.patch.object(jax, "device_count", lambda: len(devices)), \
            mock.patch.object(jax, "make_mesh", functools.partial(
                jax.make_mesh, devices=devices)):
        return make_serving_mesh(spec)


def _engine(cfg, params, ecfg, mesh):
    import jax

    from repro.serving import Engine, EngineConfig

    if mesh is None:
        return Engine(cfg, params, EngineConfig(**ecfg))
    # a sharded engine places its weights with device_put, which takes
    # arrays only; traced, it takes the described shapes
    built = []
    jax.eval_shape(lambda p: built.append(
        Engine(cfg, p, EngineConfig(**ecfg), mesh=mesh)) or 0, params)
    return built[0]


def compile_cell(cell, topo, seconds: float, log=print) -> dict:
    """Compile every prefill the cell's mix sends and its decode tick for
    the described ``topo``; returns (and logs) their memory."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.runtime import sharding as shr
    from repro.serving.cache import make_paged_cache

    from bench import harness, traffic_gen, weights

    cfg = harness.build_cfg(cell)
    harness._check_sizes(cell, cfg)
    arch = harness.arch_sizes(cfg, cell.config)
    abstract = weights.abstract(arch, jnp.dtype(cfg.param_dtype))
    if cell.chips == 1:
        mesh = None
        one = SingleDeviceSharding(topo.devices[0])
        param_sh = jax.tree.map(lambda _: one, abstract)
    else:
        mesh = described_mesh(cell.config["mesh"],
                              topo.devices[:cell.chips])
        one = NamedSharding(mesh, P())
        param_sh = shr.tree_shardings(mesh, abstract)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, param_sh)
    ecfg = dict(cell.config.get("engine", {}))
    ecfg.update(cell.mix.get("engine", {}))
    eng = _engine(cfg, params, ecfg, mesh)
    n = eng.ecfg.n_slots
    cache = jax.eval_shape(lambda: make_paged_cache(
        cfg, n, eng._n_pages, eng.ecfg.page_size, jnp.dtype(cfg.dtype)))
    cache_sh = (eng._cache_sh if mesh is not None
                else jax.tree.map(lambda _: one, cache))
    cache = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        cache, cache_sh)
    # the data first: a program that does not fit raises, and says so
    report = {"workload": cell.name,
              "params_bytes": _device_bytes(jax.tree.leaves(params)),
              "arena_bytes": _device_bytes(jax.tree.leaves(cache))}
    if mesh is not None:
        report["mesh"] = dict(mesh.shape)
    log(json.dumps(report))
    for s in traffic_gen.used_prompt_lengths(cell.mix, seconds):
        batch = {"tokens": jax.ShapeDtypeStruct((1, s), jnp.int32,
                                                sharding=one)}
        c = eng._prefill.lower(params, batch).compile()
        report[f"prefill_{s}"] = _mem(c)
        log(json.dumps({f"prefill_{s}": report[f"prefill_{s}"]}))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=one)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
    tick = eng._tick_fn(False, 0, eng.ecfg.numeric_guard)
    c = tick.lower(params, cache, i32(n, eng._pages_per_slot), i32(n),
                   i32(n, 1), jax.ShapeDtypeStruct((n,), jnp.float32,
                                                   sharding=one),
                   i32(n), i32(n), key).compile()
    report["tick"] = _mem(c)
    log(json.dumps(report))
    return report


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    from bench import harness

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    compile_cell(cell, topo, args.seconds,
                 log=functools.partial(print, flush=True))


if __name__ == "__main__":
    main()
