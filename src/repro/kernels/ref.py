"""Pure-jnp oracles for every Pallas kernel in this package.

Each oracle is the straightforward jnp expression of what the kernel must
compute.  Where the kernel's arithmetic is Goldschmidt-based, the oracle
routes through :mod:`repro.core.goldschmidt` (frexp/ldexp normalization) —
mathematically identical to the kernels' bitwise normalization, so kernels
are asserted ``allclose`` within a couple of float ulps, and both are
asserted against exact numpy division at the accuracy the seed/iteration
count guarantees.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import goldschmidt as gs

DEFAULT_P = gs.DEFAULT_P


def reciprocal(x: jnp.ndarray, *, p: int = DEFAULT_P, iters: int = 2,
               variant: str = "feedback") -> jnp.ndarray:
    return gs.gs_reciprocal(x, p=p, iters=iters, variant=variant)


def rsqrt(x: jnp.ndarray, *, p: int = DEFAULT_P, iters: int = 2,
          variant: str = "feedback") -> jnp.ndarray:
    return gs.gs_rsqrt(x, p=p, iters=iters, variant=variant)


def softmax(x: jnp.ndarray, *, p: int = DEFAULT_P, iters: int = 2,
            variant: str = "feedback") -> jnp.ndarray:
    """Row softmax over the last axis with a Goldschmidt denominator."""
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x.astype(jnp.float32) - m.astype(jnp.float32))
    s = jnp.sum(e, axis=-1, keepdims=True)
    return (e * gs.gs_reciprocal(s, p=p, iters=iters, variant=variant)).astype(x.dtype)


def softmax_exact(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def rmsnorm(x: jnp.ndarray, gain: jnp.ndarray, *, eps: float = 1e-6,
            p: int = DEFAULT_P, iters: int = 2,
            variant: str = "feedback") -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    inv = gs.gs_rsqrt(ms + eps, p=p, iters=iters, variant=variant)
    return (x32 * inv * gain.astype(jnp.float32)).astype(x.dtype)


def rmsnorm_exact(x: jnp.ndarray, gain: jnp.ndarray, *, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * gain.astype(jnp.float32)).astype(x.dtype)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, sm_scale: Optional[float] = None,
              p: int = DEFAULT_P, iters: int = 2,
              variant: str = "feedback") -> jnp.ndarray:
    """Dense GQA attention oracle.  q: (B, H, S, D); k/v: (B, KH, S, D)."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    group = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32).reshape(b, kh, group, s, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bkgsd,bktd->bkgst", qf, kf) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    ssum = jnp.sum(e, axis=-1, keepdims=True)
    probs = e * gs.gs_reciprocal(ssum, p=p, iters=iters, variant=variant)
    out = jnp.einsum("bkgst,bktd->bkgsd", probs, vf)
    return out.reshape(b, h, s, d).astype(q.dtype)


def attention_exact(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, sm_scale: Optional[float] = None) -> jnp.ndarray:
    # HIGHEST: a TPU's default matmul rounds float32 operands to bf16
    hi = jax.lax.Precision.HIGHEST
    b, h, s, d = q.shape
    kh = k.shape[1]
    group = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32).reshape(b, kh, group, s, d)
    logits = jnp.einsum("bkgsd,bktd->bkgst", qf, k.astype(jnp.float32),
                        precision=hi) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", probs, v.astype(jnp.float32),
                     precision=hi)
    return out.reshape(b, h, s, d).astype(q.dtype)


def adam_update(param, grad, m, v, *, lr: float, beta1: float = 0.9,
                beta2: float = 0.999, eps: float = 1e-8,
                weight_decay: float = 0.0, step: int = 1,
                p: int = DEFAULT_P, iters: int = 2,
                variant: str = "feedback"):
    """AdamW update with Goldschmidt sqrt + reciprocal for the denominator."""
    g32 = grad.astype(jnp.float32)
    m_new = beta1 * m + (1.0 - beta1) * g32
    v_new = beta2 * v + (1.0 - beta2) * g32 * g32
    bc1 = 1.0 / (1.0 - beta1 ** step)
    bc2 = 1.0 / (1.0 - beta2 ** step)
    denom = gs.gs_sqrt(v_new * bc2, p=p, iters=iters, variant=variant) + eps
    update = (m_new * bc1) * gs.gs_reciprocal(denom, p=p, iters=iters, variant=variant)
    p_new = param.astype(jnp.float32) - lr * (update + weight_decay * param.astype(jnp.float32))
    return p_new.astype(param.dtype), m_new, v_new


def adam_update_exact(param, grad, m, v, *, lr: float, beta1: float = 0.9,
                      beta2: float = 0.999, eps: float = 1e-8,
                      weight_decay: float = 0.0, step: int = 1):
    g32 = grad.astype(jnp.float32)
    m_new = beta1 * m + (1.0 - beta1) * g32
    v_new = beta2 * v + (1.0 - beta2) * g32 * g32
    bc1 = 1.0 / (1.0 - beta1 ** step)
    bc2 = 1.0 / (1.0 - beta2 ** step)
    update = (m_new * bc1) / (jnp.sqrt(v_new * bc2) + eps)
    p_new = param.astype(jnp.float32) - lr * (update + weight_decay * param.astype(jnp.float32))
    return p_new.astype(param.dtype), m_new, v_new
