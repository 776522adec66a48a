"""RMSNorm / LayerNorm with Goldschmidt rsqrt (division site #2).

fp32 statistics regardless of activation dtype.  The mean is a multiply by
the compile-time constant 1/d (no runtime divide); the rsqrt is the
policy's — i.e. [4]'s coupled Goldschmidt iteration under ``gs_*`` modes.
``kernel_impl='pallas'`` routes RMSNorm through the fused Pallas kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.policy import NumericsPolicy


def rmsnorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def layernorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


@jax.named_scope("rmsnorm")
def rmsnorm(params, x, *, eps: float, policy: NumericsPolicy,
            kernel_impl: str = "jnp"):
    if kernel_impl == "pallas":
        from repro.kernels import ops

        if policy.is_fixed:
            # int8 datapath: quantize the activation per-tensor at the
            # norm boundary and run the fused fixed-point kernel — the
            # scale reciprocal is itself a policy division site.
            x32 = x.astype(jnp.float32)
            amax = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-6)
            inv_amax = policy.reciprocal(amax)
            xq = jnp.clip(jnp.round(x32 * (127.0 * inv_amax)),
                          -127.0, 127.0).astype(jnp.int8)
            out = ops.gs_fixed_rmsnorm(
                xq, amax * (1.0 / 127.0), params["scale"], eps=eps,
                variant=policy.variant, **policy.fmt.precision(),
            )
            return out.astype(x.dtype)
        # block_rows / interpret resolve through the tuning dispatch; the
        # policy pins the datapath variant and the (ROM width, iteration
        # count) pair whenever its accuracy budget differs from x's dtype
        # — otherwise they derive from the dtype (bf16 activations run
        # the seed-only datapath) and stay autotunable.
        return ops.gs_rmsnorm(
            x, params["scale"], eps=eps, variant=policy.variant,
            **policy.kernel_precision(x.dtype),
        )
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * policy.rsqrt(ms + eps) * params["scale"]).astype(x.dtype)


def layernorm(params, x, *, eps: float, policy: NumericsPolicy,
              kernel_impl: str = "jnp"):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * policy.rsqrt(var + eps) * params["scale"] + params["bias"]).astype(
        x.dtype
    )


def norm_init(kind: str, d: int):
    return layernorm_init(d) if kind == "layernorm" else rmsnorm_init(d)


def norm_apply(kind: str, params, x, *, eps, policy, kernel_impl="jnp"):
    if kind == "layernorm":
        return layernorm(params, x, eps=eps, policy=policy, kernel_impl=kernel_impl)
    return rmsnorm(params, x, eps=eps, policy=policy, kernel_impl=kernel_impl)
