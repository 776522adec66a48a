"""Rotary position embeddings: standard RoPE and Qwen2-VL's M-RoPE.

M-RoPE splits the rotary feature pairs into (temporal, height, width)
sections, each driven by its own position-id stream — ``pos_ids`` has
shape (3, b, s).  For text-only input the three streams coincide and
M-RoPE degenerates to RoPE (tested).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _angles(positions: jnp.ndarray, dim_half: int, theta: float) -> jnp.ndarray:
    """positions (...,) -> angles (..., dim_half)."""
    inv_freq = theta ** (-jnp.arange(0, dim_half, dtype=jnp.float32) / dim_half)
    return positions[..., None].astype(jnp.float32) * inv_freq


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float):
    """positions (b, s) -> cos/sin (b, s, head_dim//2)."""
    ang = _angles(positions, head_dim // 2, theta)
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(pos_ids: jnp.ndarray, head_dim: int, theta: float,
                  sections: Tuple[int, int, int]):
    """pos_ids (3, b, s) -> cos/sin (b, s, head_dim//2) with sectioned freqs."""
    dim_half = head_dim // 2
    assert sum(sections) == dim_half, (sections, dim_half)
    inv_freq = theta ** (-jnp.arange(0, dim_half, dtype=jnp.float32) / dim_half)
    ang_tsw = pos_ids[..., None].astype(jnp.float32) * inv_freq  # (3, b, s, H/2)
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=dim_half
    )  # static per-feature section id
    select = (sec_id[None, :] == jnp.arange(3)[:, None]).astype(jnp.float32)
    ang = jnp.einsum("tbsh,th->bsh", ang_tsw, select)
    return jnp.cos(ang), jnp.sin(ang)


@jax.named_scope("rope")
def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x (b, s, h, d); cos/sin (b, s, d//2).  Rotate-half convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
