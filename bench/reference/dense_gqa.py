"""Plain float32 reference of a dense GQA decoder (Llama-style block,
with Granite's scalars where the configuration states them).

    x = embed[tokens] * m_emb
    per layer:  h = rmsnorm(x) * g1
                q, k, v = h Wq, h Wk, h Wv         (rotary on q and k)
                x = x + m_res * softmax(q k^T * m_attn, causal) v  Wo
                h = rmsnorm(x) * g2
                x = x + m_res * (silu(h Wgate) * (h Win)) Wout
    logits = (rmsnorm(x) * g) Whead / s_logits      (Whead = embed^T if tied)

The arithmetic comes from the configuration file's published keys
(``arithmetic``): ``rms_norm_eps`` and ``rope_theta`` always;
``embedding_multiplier`` (m_emb), ``attention_multiplier`` (m_attn),
``residual_multiplier`` (m_res), ``logits_scaling`` (s_logits) and
``tie_word_embeddings`` where it states them.  A scalar it does not state
is no operation at all (not a multiply by 1), and m_attn is then
``1 / sqrt(hd)``.

Grouped-query attention: query head ``i`` reads key/value head
``i // (H / KH)``.  Rotary embedding in the rotate-half form with
frequencies ``theta ** (-2j / hd)``.  Every matmul runs at the highest
precision, in float32, with no kernel, cache or batching; it imports
nothing of the program.  Layers run one at a time (a scan) and the
attention in blocks of query rows, so a long sequence fits one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
REQUIRED = ("rms_norm_eps", "rope_theta")
OPTIONAL = ("embedding_multiplier", "attention_multiplier",
            "residual_multiplier", "logits_scaling", "tie_word_embeddings")


def arithmetic(config: dict) -> tuple:
    """The published keys of a configuration file that the reference
    computes with, as sorted ``(key, value)`` pairs (hashable, so a jit
    can take them as static)."""
    missing = [k for k in REQUIRED if k not in config]
    if missing:
        raise KeyError(f"the configuration states no {', '.join(missing)}")
    return tuple(sorted((k, config[k]) for k in REQUIRED + OPTIONAL
                        if k in config))


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, hd/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, scale=None):
    """Causal GQA attention in blocks of query rows.  q (S, H, hd),
    k/v (S, KH, hd); S is a multiple of Q_BLOCK.  Scores are scaled by
    ``scale``, or by ``1 / sqrt(hd)`` where it is None."""
    S, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    cols = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = _ein("qhd,khd->hqk", qb, k) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return out.reshape(S, H, hd)


def padded_len(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


@functools.partial(jax.jit, static_argnames=("n_out", "arch"))
def logits_at(params, tokens, start, *, n_out: int, arch: tuple):
    """Logits (n_out, V) at positions ``start .. start + n_out - 1`` of
    ``tokens`` (S,), S a multiple of Q_BLOCK, with the arithmetic
    ``arch`` (from ``arithmetic``).  Positions past the real sequence may
    hold any token: attention is causal."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    a = dict(arch)
    eps, theta = float(a["rms_norm_eps"]), float(a["rope_theta"])
    m_emb, m_attn, m_res, s_logits = (
        a.get("embedding_multiplier"), a.get("attention_multiplier"),
        a.get("residual_multiplier"), a.get("logits_scaling"))
    scale = None if m_attn is None else jnp.float32(m_attn)

    def res(y):
        return y if m_res is None else y * jnp.float32(m_res)

    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = f32(params["embed"])[tokens]
    if m_emb is not None:
        x = x * jnp.float32(m_emb)

    def layer(x, p):
        h = _rmsnorm(x, f32(p["norm1"]["scale"]), eps)
        at = p["attn"]
        q = _rope(_ein("sd,dhk->shk", h, f32(at["wq"])), pos, theta)
        k = _rope(_ein("sd,dhk->shk", h, f32(at["wk"])), pos, theta)
        v = _ein("sd,dhk->shk", h, f32(at["wv"]))
        x = x + res(_ein("shk,hkd->sd", _attention(q, k, v, scale),
                         f32(at["wo"])))
        h = _rmsnorm(x, f32(p["norm2"]["scale"]), eps)
        m = p["mlp"]
        u = (jax.nn.silu(_ein("sd,df->sf", h, f32(m["w_gate"])))
             * _ein("sd,df->sf", h, f32(m["w_in"])))
        return x + res(_ein("sf,fd->sd", u, f32(m["w_out"]))), None

    x, _ = jax.lax.scan(layer, x, params["layers"]["pos0"])
    x = jax.lax.dynamic_slice_in_dim(x, start, n_out, 0)
    h = _rmsnorm(x, f32(params["final_norm"]["scale"]), eps)
    if a.get("tie_word_embeddings"):
        logits = _ein("sd,vd->sv", h, f32(params["embed"]))
    else:
        logits = _ein("sd,dv->sv", h, f32(params["lm_head"]))
    if s_logits is not None:
        logits = logits / jnp.float32(s_logits)
    return logits


def served_gaps(params, seq, prompt_len: int, served, *, n_out: int,
                arch: tuple):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the reference agrees).

    ``seq`` is prompt + served tokens, padded to a multiple of Q_BLOCK;
    token ``i`` of ``served`` was produced at position
    ``prompt_len - 1 + i``.  Returns a float64 numpy array, one gap per
    served token."""
    import numpy as np

    lg = logits_at(params, jnp.asarray(seq, jnp.int32),
                   jnp.int32(prompt_len - 1), n_out=n_out, arch=arch)
    n = len(served)
    tok = jnp.asarray(np.pad(np.asarray(served, np.int32), (0, n_out - n)))
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
    return np.asarray(gap, np.float64)[:n]
