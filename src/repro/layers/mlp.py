"""Gated MLP (SwiGLU / GELU)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.layers import init as linit


def mlp_init(rng, d_model: int, d_ff: int, act: str = "silu"):
    r = jax.random.split(rng, 3)
    p = {
        "w_in": linit.dense_init(r[0], d_model, (d_model, d_ff)),
        "w_out": linit.dense_init(r[1], d_ff, (d_ff, d_model)),
    }
    if act == "silu":  # gated
        p["w_gate"] = linit.dense_init(r[2], d_model, (d_model, d_ff))
    return p


@jax.named_scope("mlp")
def mlp_apply(params, x, *, act: str = "silu"):
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, linit.cast(params["w_in"], dt))
    if act == "silu":
        gate = jnp.einsum("bsd,df->bsf", x, linit.cast(params["w_gate"], dt))
        h = jax.nn.silu(gate) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, linit.cast(params["w_out"], dt))
