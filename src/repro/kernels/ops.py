"""Public dispatch front-end over the Pallas kernels.

Shape-polymorphic: callers hand any-shaped arrays; wrappers pad / reshape
to kernel tiling (done inside each kernel module) and restore.

Every op resolves its launch config (``variant``, block shape, the ROM
width ``p``, ``iters``, interpret-vs-compiled) through
:mod:`repro.kernels.tuning` at trace time: explicit kwargs win, then —
when tuning is enabled via ``REPRO_AUTOTUNE=1`` or
``tuning.enable_tuning()`` — the persisted autotune cache for this
``(kernel, shape-bucket, dtype, backend)``, then the registry defaults.
Defaults leave ``(p, iters)`` to the operand dtype's
:func:`repro.core.goldschmidt.precision_policy` pair: fp32 resolves to
the seed literals (7, 2) — cold-start fp32 behavior is bit-identical —
while bf16 runs seed-only (8, 0) and fp16 single-pass (7, 1).

``interpret`` is derived from the backend
(:func:`repro.kernels.common.interpret_flag`): on the CPU backend (tests,
``JAX_PLATFORMS=cpu``) the kernels run in Pallas interpret mode; on a TPU
the same BlockSpecs compile through Mosaic.  An explicit
``interpret=False`` compiles for a described chip from a CPU host
(``tests/test_tpu_compile.py``).

On a mesh (an activation context of :mod:`repro.runtime.sharding` is
active, as inside the sharded serving and training steps) the ops the
model calls run under ``jax.shard_map``: GSPMD has no partitioning rule
for a Pallas call, so each chip runs the kernel on its own shard.  The
rmsnorms (float and int8) shard their leading (batch) dimension over the
context's data axes and keep the rest whole (the residual stream is
replicated over 'model'); flash attention shards heads over 'model'.
With no context the call is the plain kernel call.

Every front-end routes through :func:`dispatch.call_with_fallback`.  By
default a kernel that fails to trace/lower/compile raises.  With the
fallback opted in (``REPRO_KERNEL_FALLBACK=1`` or
``dispatch.enable_fallback(True)``) it downgrades to its jnp oracle
(:mod:`repro.kernels.ref`; exact-arithmetic references for the
fixed-point kernels) instead, counted per kernel
(``dispatch.fallback_stats()``; surfaced as
``ServeMetrics.kernel_fallbacks``).

All ops are differentiable: each kernel carries a ``custom_vjp`` whose
rule runs on saved forward outputs (quotient / rsqrt / softmax /
(m, l) attention statistics) instead of autodiffing the Goldschmidt
``fori_loop`` or the bitcast field peel, so ``jax.grad`` through
``kernel_impl='pallas'`` matches the jnp reference path.  Flash
attention's backward tile shapes resolve through the dispatch under the
``flash_attention_bwd`` registry entry (override with
``block_q_bwd``/``block_kv_bwd``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import common
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.gs_adam import gs_adam_update as _gs_adam_update
from repro.kernels.gs_fixed import gs_fixed_recip as _gs_fixed_recip
from repro.kernels.gs_fixed import gs_fixed_rmsnorm as _gs_fixed_rmsnorm
from repro.kernels.gs_fixed import gs_fixed_softmax as _gs_fixed_softmax
from repro.kernels.gs_recip import gs_recip as _gs_recip
from repro.kernels.gs_rmsnorm import gs_rmsnorm as _gs_rmsnorm
from repro.kernels.gs_rsqrt import gs_rsqrt as _gs_rsqrt
from repro.kernels.gs_rsqrt import gs_sqrt as _gs_sqrt
from repro.kernels.gs_softmax import gs_softmax as _gs_softmax
from repro.kernels.tuning import dispatch
from repro.runtime import sharding as shr

__all__ = [
    "flash_attention",
    "gs_adam_update",
    "gs_fixed_recip",
    "gs_fixed_rmsnorm",
    "gs_fixed_softmax",
    "gs_recip",
    "gs_rmsnorm",
    "gs_rsqrt",
    "gs_softmax",
    "gs_sqrt",
]


def _on_mesh(body, args, specs):
    """``body(*args)``; inside an activation context, under
    ``jax.shard_map`` over its mesh with ``specs(mesh, dp)`` =
    ``(in_specs, out_specs)``."""
    ctx = shr.active_context()
    if ctx is None:
        return body(*args)
    mesh, dp = ctx
    in_specs, out_specs = specs(mesh, dp)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _rows(body, x, *rest):
    """A row-wise op ``body(x, *rest)``: ``x`` and the output shard their
    leading dimension over the data axes, ``rest`` (the gain, the int8
    scale) replicate."""
    def specs(mesh, dp):
        spec = shr.filter_pspec(P(dp or None), mesh, x.shape)
        return (spec,) + (P(),) * len(rest), spec

    return _on_mesh(body, (x,) + rest, specs)


def _gs_kw(cfg):
    """The Goldschmidt-math subset of a launch config — what the jnp
    oracles accept (tiling/interpret keys are kernel-only)."""
    return {k: cfg[k] for k in ("p", "iters", "variant") if k in cfg}


def gs_recip(x, *, p: int | None = None, **config):
    cfg = dispatch.resolve("gs_recip", x.shape, x.dtype, {"p": p, **config})
    return dispatch.call_with_fallback(
        "gs_recip", lambda: _gs_recip(x, **cfg),
        lambda: _ref.reciprocal(x, **_gs_kw(cfg)))


def gs_rsqrt(x, *, p: int | None = None, **config):
    cfg = dispatch.resolve("gs_rsqrt", x.shape, x.dtype, {"p": p, **config})
    return dispatch.call_with_fallback(
        "gs_rsqrt", lambda: _gs_rsqrt(x, **cfg),
        lambda: _ref.rsqrt(x, **_gs_kw(cfg)))


def gs_sqrt(x, *, p: int | None = None, **config):
    # Same datapath, ROM, and tiling as rsqrt — shares its tuning entry.
    cfg = dispatch.resolve("gs_rsqrt", x.shape, x.dtype, {"p": p, **config})
    from repro.core import goldschmidt as _gs

    return dispatch.call_with_fallback(
        "gs_sqrt", lambda: _gs_sqrt(x, **cfg),
        lambda: _gs.gs_sqrt(x, **_gs_kw(cfg)))


def gs_softmax(x, *, p: int | None = None, **config):
    cfg = dispatch.resolve("gs_softmax", x.shape, x.dtype, {"p": p, **config})
    return dispatch.call_with_fallback(
        "gs_softmax", lambda: _gs_softmax(x, **cfg),
        lambda: _ref.softmax(x, **_gs_kw(cfg)))


def gs_rmsnorm(x, gain, *, eps: float = 1e-6, p: int | None = None,
               **config):
    def body(x, gain):
        cfg = dispatch.resolve("gs_rmsnorm", x.shape, x.dtype,
                               {"p": p, **config})
        return dispatch.call_with_fallback(
            "gs_rmsnorm", lambda: _gs_rmsnorm(x, gain, eps=eps, **cfg),
            lambda: _ref.rmsnorm(x, gain, eps=eps, **_gs_kw(cfg)))

    return _rows(body, x, gain)


# -- fixed-point (int8) epilogues -------------------------------------------
# Same resolution path as the float kernels; ``frac_bits``/``mitchell_iters``
# join (p, iters) as tunable axes, derived from the measured int8 frontier
# (repro.core.formats) when unpinned.


def gs_fixed_recip(x, scale=1.0, *, p: int | None = None, **config):
    cfg = dispatch.resolve("gs_fixed_recip", x.shape, x.dtype,
                           {"p": p, **config})
    # Fixed-kernel fallbacks are the exact float expression of the op's
    # contract (f(x * scale) in f32) — the degraded path trades the
    # multiplier-only datapath for accuracy, never the reverse.
    return dispatch.call_with_fallback(
        "gs_fixed_recip", lambda: _gs_fixed_recip(x, scale, **cfg),
        lambda: 1.0 / (x.astype(jnp.float32) * scale))


def gs_fixed_softmax(x, scale=1.0, *, p: int | None = None, **config):
    cfg = dispatch.resolve("gs_fixed_softmax", x.shape, x.dtype,
                           {"p": p, **config})
    return dispatch.call_with_fallback(
        "gs_fixed_softmax", lambda: _gs_fixed_softmax(x, scale, **cfg),
        lambda: jax.nn.softmax(x.astype(jnp.float32) * scale, axis=-1))


def _fixed_rmsnorm_ref(x, scale, gain, eps):
    xf = x.astype(jnp.float32) * scale
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * gain.astype(jnp.float32)


def gs_fixed_rmsnorm(x, scale, gain, *, eps: float = 1e-6,
                     p: int | None = None, **config):
    def body(x, scale, gain):
        cfg = dispatch.resolve("gs_fixed_rmsnorm", x.shape, x.dtype,
                               {"p": p, **config})
        return dispatch.call_with_fallback(
            "gs_fixed_rmsnorm", lambda: _gs_fixed_rmsnorm(x, scale, gain,
                                                          eps=eps, **cfg),
            lambda: _fixed_rmsnorm_ref(x, scale, gain, eps))

    return _rows(body, x, jnp.asarray(scale, jnp.float32), gain)


def gs_adam_update(param, grad, m, v, step, *, lr, beta1: float = 0.9,
                   beta2: float = 0.999, eps: float = 1e-8,
                   weight_decay: float = 0.0, p: int | None = None,
                   **config):
    cfg = dispatch.resolve("gs_adam", param.shape, param.dtype,
                           {"p": p, **config})
    return dispatch.call_with_fallback(
        "gs_adam",
        lambda: _gs_adam_update(param, grad, m, v, step, lr=lr, beta1=beta1,
                                beta2=beta2, eps=eps,
                                weight_decay=weight_decay, **cfg),
        lambda: _ref.adam_update(param, grad, m, v, lr=lr, beta1=beta1,
                                 beta2=beta2, eps=eps,
                                 weight_decay=weight_decay, step=step,
                                 **_gs_kw(cfg)))


def flash_attention(q, k, v, *, causal: bool = True, sm_scale=None,
                    p: int | None = None, **config):
    def body(q, k, v):
        cfg = dispatch.resolve("flash_attention", q.shape, q.dtype,
                               {"p": p, **config})
        # Tuned/default blocks come from a pow2 shape bucket, so clamp
        # them to tile the actual sequence length — but never rewrite a
        # block size the caller passed explicitly (the kernel's
        # divisibility assert applies).
        s = q.shape[2]
        for key in ("block_q", "block_kv"):
            if config.get(key) is None:
                cfg[key] = common.fit_block(s, cfg[key])
        return dispatch.call_with_fallback(
            "flash_attention",
            lambda: _flash_attention(q, k, v, causal=causal,
                                     sm_scale=sm_scale, **cfg),
            lambda: _ref.attention(q, k, v, causal=causal,
                                   sm_scale=sm_scale, **_gs_kw(cfg)))

    ctx = shr.active_context()
    if ctx is not None:
        # heads over 'model', contiguously: chip i's query heads read
        # chip i's KV heads.  Where the KV heads do not divide the axis,
        # each query head gets its own copy of its KV head (head i reads
        # i // group, as the kernel's index map does)
        h, kh = q.shape[1], k.shape[1]
        model = dict(ctx[0].shape).get("model", 1)
        if kh % model and not h % model:
            k, v = (jnp.repeat(a, h // kh, axis=1) for a in (k, v))
    q_spec = lambda mesh, dp: shr.filter_pspec(  # noqa: E731
        P(dp or None, "model"), mesh, q.shape)
    kv_spec = lambda mesh, dp: shr.filter_pspec(  # noqa: E731
        P(dp or None, "model"), mesh, k.shape)
    return _on_mesh(body, (q, k, v), lambda mesh, dp: (
        (q_spec(mesh, dp), kv_spec(mesh, dp), kv_spec(mesh, dp)),
        q_spec(mesh, dp)))
