"""Compile every Pallas kernel for a described TPU v5e, without a chip.

The kernels run interpreted in the rest of the suite (CPU backend).  Mosaic
refuses layouts, casts and VMEM budgets that interpret mode accepts, so
each kernel here is lowered with ``interpret=False`` at internlm2-1.8b
widths (d_model 2048, 16 q heads / 8 kv heads, head_dim 128, vocab 92544)
and compiled for one chip of a ``v5e:2x2`` topology.  A compile that
passes shows the kernel is a ``tpu_custom_call``; it says nothing about
results or times, which only a chip run gives.  The engine's paged decode
tick is compiled the same way, at 2 layers, to check where it keeps the
KV arena.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.models import api
from repro.serving import Engine, EngineConfig
from repro.serving.cache import make_paged_cache

D_MODEL = 2048
N_HEADS, N_KV_HEADS, HEAD_DIM = 16, 8, 128
VOCAB = 92544


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("rows", [256, 37, 1])
def test_gs_rmsnorm(one_chip, rows):
    _compile(lambda x, g: ops.gs_rmsnorm(x, g, interpret=False), one_chip,
             ((rows, D_MODEL), jnp.bfloat16), ((D_MODEL,), jnp.float32))


@pytest.mark.parametrize("seq", [37, 512, 2048])
def test_flash_attention_forward(one_chip, seq):
    q = ((1, N_HEADS, seq, HEAD_DIM), jnp.bfloat16)
    kv = ((1, N_KV_HEADS, seq, HEAD_DIM), jnp.bfloat16)
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
             one_chip, q, kv, kv)


def test_flash_attention_backward(one_chip):
    q = ((1, N_HEADS, 512, HEAD_DIM), jnp.bfloat16)
    kv = ((1, N_KV_HEADS, 512, HEAD_DIM), jnp.bfloat16)
    loss = lambda q, k, v: ops.flash_attention(  # noqa: E731
        q, k, v, interpret=False).astype(jnp.float32).sum()
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    assert text.count("tpu_custom_call") >= 3  # forward, dq, dk/dv


def test_gs_softmax_vocab(one_chip):
    _compile(lambda x: ops.gs_softmax(x, interpret=False), one_chip,
             ((256, VOCAB), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gs_recip(one_chip, dtype):
    _compile(lambda x: ops.gs_recip(x, interpret=False), one_chip,
             ((256, D_MODEL), dtype))


def test_gs_rsqrt(one_chip):
    _compile(lambda x: ops.gs_rsqrt(x, interpret=False), one_chip,
             ((256, D_MODEL), jnp.float32))


def test_gs_adam(one_chip):
    w = ((D_MODEL, D_MODEL), jnp.float32)
    _compile(lambda p, g, m, v, s: ops.gs_adam_update(
        p, g, m, v, s, lr=1e-3, interpret=False), one_chip,
        w, w, w, w, ((), jnp.int32))


@pytest.mark.parametrize("mitchell_iters", [0, 1])
def test_gs_fixed_recip(one_chip, mitchell_iters):
    # mitchell_iters=1 runs the log-multiplier on the first of 2 passes
    _compile(lambda x: ops.gs_fixed_recip(
        x, 0.02, p=7, iters=2, mitchell_iters=mitchell_iters,
        interpret=False), one_chip, ((256, D_MODEL), jnp.int8))


def test_gs_fixed_softmax(one_chip):
    _compile(lambda x: ops.gs_fixed_softmax(x, 0.03, interpret=False),
             one_chip, ((64, VOCAB), jnp.int8))


def test_gs_fixed_rmsnorm(one_chip):
    _compile(lambda x, g: ops.gs_fixed_rmsnorm(x, 0.03, g, interpret=False),
             one_chip, ((256, D_MODEL), jnp.int8), ((D_MODEL,), jnp.float32))


def test_paged_tick_updates_arena_in_place(one_chip):
    """The decode tick at internlm2-1.8b widths (2 layers, a 65-page
    arena) updates the donated KV arena in place: the optimized HLO holds
    no copy of a whole KV stack, and the whole cache is aliased to the
    tick's cache output."""
    cfg = configs.get_config("internlm2-1.8b", n_layers=2)
    n, ps = 4, 16
    sds = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, api.param_specs(cfg))
    eng = Engine(cfg, params, EngineConfig(
        n_slots=n, s_max=256, pool="paged", page_size=ps, n_pages=65))
    cache = jax.tree.map(sds, jax.eval_shape(lambda: make_paged_cache(
        cfg, n, eng._n_pages, ps, jnp.dtype(cfg.dtype))))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    compiled = eng._tick_fn(False, 0, True).lower(
        params, cache, i32(n, eng._pages_per_slot), i32(n), i32(n, 1),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
        i32(n), i32(n),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                             sharding=one_chip)).compile()
    leaves = jax.tree.leaves(cache)
    stack = "[" + ",".join(map(str, leaves[0].shape)) + "]"
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r" copy(-start)?\(", line)
              and stack in re.split(r" copy(-start)?\(", line)[0]]
    assert not copies, copies[:2]
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in leaves)
