"""Encoder-decoder backbone (Whisper-style) with the stub audio frontend.

Per the assignment, the conv frontend is a STUB: ``input_specs`` feeds
precomputed (b, enc_seq, d_model) frame embeddings.  Everything else is
real: sinusoidal encoder positions, non-causal encoder self-attention,
causal decoder self-attention with KV cache, per-layer cross-attention
over the encoder output (cross-KV cached at prefill), learned decoder
positions, LayerNorm (Goldschmidt rsqrt on the variance), tied unembed.

Both stacks scan over layers like the decoder-only model.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.layers import attention as attn
from repro.layers import init as linit
from repro.layers import mlp as mlp_mod
from repro.layers.norms import norm_apply, norm_init

Params = Dict[str, Any]


def _sinusoid(n: int, d: int) -> jnp.ndarray:
    # Host-side NumPy on purpose: this is a static (n, d) compile-time
    # constant, and leaving it as traced iota+concatenate lets GSPMD
    # partition the concat — which XLA CPU SPMD miscompiles when a shard
    # boundary lands exactly on the sin/cos seam (observed as wrong
    # encoder halves under the TP serving mesh; see
    # tests/test_multidevice.py sharded-serving family parity).
    pos = np.arange(n, dtype=np.float32)[:, None]
    dim = np.arange(d // 2, dtype=np.float32)[None, :]
    inv = np.exp(-dim * (np.log(10000.0) / (d // 2 - 1)))
    ang = pos * inv
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], axis=-1))


def _enc_layer_init(rng, cfg: ArchConfig):
    r = jax.random.split(rng, 2)
    return {
        "norm1": norm_init(cfg.norm, cfg.d_model),
        "attn": attn.attn_init(r[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim_),
        "norm2": norm_init(cfg.norm, cfg.d_model),
        "mlp": mlp_mod.mlp_init(r[1], cfg.d_model, cfg.d_ff, cfg.act),
    }


def _dec_layer_init(rng, cfg: ArchConfig):
    r = jax.random.split(rng, 3)
    return {
        "norm1": norm_init(cfg.norm, cfg.d_model),
        "self_attn": attn.attn_init(r[0], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.head_dim_),
        "norm2": norm_init(cfg.norm, cfg.d_model),
        "cross_attn": attn.attn_init(r[1], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim_),
        "norm3": norm_init(cfg.norm, cfg.d_model),
        "mlp": mlp_mod.mlp_init(r[2], cfg.d_model, cfg.d_ff, cfg.act),
    }


def init(cfg: ArchConfig, rng) -> Params:
    r = jax.random.split(rng, 5)
    return {
        "embed": linit.trunc_normal(r[0], (cfg.vocab, cfg.d_model), 0.02),
        "pos_embed": linit.trunc_normal(r[1], (cfg.max_seq, cfg.d_model), 0.02),
        "enc_layers": linit.stacked(
            r[2], cfg.n_enc_layers, lambda rr: _enc_layer_init(rr, cfg)
        ),
        "dec_layers": linit.stacked(
            r[3], cfg.n_layers, lambda rr: _dec_layer_init(rr, cfg)
        ),
        "enc_final_norm": norm_init(cfg.norm, cfg.d_model),
        "final_norm": norm_init(cfg.norm, cfg.d_model),
    }


def encode(cfg: ArchConfig, params: Params, frames: jnp.ndarray) -> jnp.ndarray:
    """frames (b, enc_seq, d_model) -> encoder output, same shape."""
    policy = cfg.policy()
    x = (frames + _sinusoid(frames.shape[1], cfg.d_model)[None]).astype(cfg.dtype)

    def body(x, lp):
        h = norm_apply(cfg.norm, lp["norm1"], x, eps=cfg.norm_eps, policy=policy)
        q, k, v = attn.qkv(lp["attn"], h)
        o = attn.flash_chunked(q, k, v, policy=policy, causal=False,
                               q_block=cfg.attn_q_block,
                               kv_block=cfg.attn_kv_block,
                               seq_shard=cfg.attn_seq_shard)
        x = x + attn.out_proj(lp["attn"], o)
        h = norm_apply(cfg.norm, lp["norm2"], x, eps=cfg.norm_eps, policy=policy)
        x = x + mlp_mod.mlp_apply(lp["mlp"], h, act=cfg.act)
        return x, None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(fn, x, params["enc_layers"])
    return norm_apply(cfg.norm, params["enc_final_norm"], x, eps=cfg.norm_eps,
                      policy=policy)


def _dec_stack(cfg: ArchConfig, params: Params, x, enc_out, *, mode: str,
               states=None, cur_index=None, page_table=None,
               page_size: int = 0):
    policy = cfg.policy()
    has_state = mode in ("prefill", "decode", "chunk")
    consumes_state = mode in ("decode", "chunk")

    def apply(x, lp, st, layer):
        h = norm_apply(cfg.norm, lp["norm1"], x, eps=cfg.norm_eps, policy=policy)
        q, k, v = attn.qkv(lp["self_attn"], h)
        new_st = {} if has_state else None
        if mode == "decode":
            # ``st`` is the whole stacked state; this layer reads and
            # writes its own index of it in place (transformer._stack)
            if page_table is not None:
                # paged self-attention KV (shared arena, see attention.py);
                # cross-KV stays slot-indexed — it is request-specific
                # (computed from this request's frames) and full-length
                # from prefill, so paging buys nothing there.
                kc, vc = attn.paged_cache_update(
                    st["k"], st["v"], layer, k, v, page_table, cur_index,
                    page_size)
                kv = attn.gather_pages(kc, layer, page_table)
                vv = attn.gather_pages(vc, layer, page_table)
            else:
                kc, vc = attn.cache_update(st["k"], st["v"], layer, k, v,
                                           cur_index)
                kv, vv = kc[layer], vc[layer]
            o = attn.decode_attention(q, kv, vv, cur_index, policy=policy)
            new_st = {"k": kc, "v": vc, "ck": st["ck"], "cv": st["cv"]}
            ck, cv = st["ck"][layer], st["cv"][layer]
        elif mode == "chunk":
            # chunked prefill: append this chunk's self-KV to the carry
            # and attend the new rows against the whole prefix; cross-KV
            # was computed once by chunk_init and rides the carry
            k_all = jnp.concatenate([st["k"], k], axis=1)
            v_all = jnp.concatenate([st["v"], v], axis=1)
            o = attn.chunk_attention(q, k_all, v_all, policy=policy)
            new_st = {"k": k_all, "v": v_all, "ck": st["ck"], "cv": st["cv"]}
            ck, cv = st["ck"], st["cv"]
        else:
            o = attn.flash_chunked(q, k, v, policy=policy, causal=True,
                                   q_block=cfg.attn_q_block,
                                   kv_block=cfg.attn_kv_block,
                                   seq_shard=cfg.attn_seq_shard)
            if mode == "prefill":
                new_st = {"k": k, "v": v}
        x = x + attn.out_proj(lp["self_attn"], o)
        h = norm_apply(cfg.norm, lp["norm2"], x, eps=cfg.norm_eps, policy=policy)
        cq = jnp.einsum("bsd,dhk->bshk", h, lp["cross_attn"]["wq"].astype(h.dtype))
        if not consumes_state:
            ck = jnp.einsum("bsd,dhk->bshk", enc_out,
                            lp["cross_attn"]["wk"].astype(h.dtype))
            cv = jnp.einsum("bsd,dhk->bshk", enc_out,
                            lp["cross_attn"]["wv"].astype(h.dtype))
            if mode == "prefill":
                new_st["ck"], new_st["cv"] = ck, cv
        if consumes_state:
            o = attn.attention_dense(cq, ck, cv, policy=policy, causal=False)
        else:
            o = attn.flash_chunked(cq, ck, cv, policy=policy, causal=False,
                                   q_block=cfg.attn_q_block,
                                   kv_block=cfg.attn_kv_block,
                                   seq_shard=cfg.attn_seq_shard)
        x = x + attn.out_proj(lp["cross_attn"], o)
        h = norm_apply(cfg.norm, lp["norm3"], x, eps=cfg.norm_eps, policy=policy)
        x = x + mlp_mod.mlp_apply(lp["mlp"], h, act=cfg.act)
        return x, new_st

    if mode == "decode":
        def body(carry, group):
            return apply(carry[0], group[0], carry[1], group[1]), None

        layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (x, new_states), _ = jax.lax.scan(
            body, (x, states), (params["dec_layers"], layers))
        return x, new_states

    def body(x, group):
        return apply(x, group[0], group[1], None)

    xs = (params["dec_layers"], states if consumes_state else None)
    fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
    x, new_states = jax.lax.scan(fn, x, xs)
    return x, (new_states if has_state else None)


def _embed_dec(cfg, params, tokens, cur_index=None):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cur_index is not None and jnp.ndim(cur_index) == 1:
        # per-slot decode positions (continuous batching): (b, 1, d)
        pe = jnp.take(params["pos_embed"], cur_index, axis=0)[:, None]
    elif cur_index is not None:
        pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], cur_index,
                                          tokens.shape[1], axis=0)[None]
    else:
        pe = params["pos_embed"][: tokens.shape[1]][None]
    return x + pe.astype(cfg.dtype)


def _unembed(cfg, params, x):
    h = norm_apply(cfg.norm, params["final_norm"], x, eps=cfg.norm_eps,
                   policy=cfg.policy())
    return jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))


def forward(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
            frames: jnp.ndarray) -> jnp.ndarray:
    enc_out = encode(cfg, params, frames)
    x = _embed_dec(cfg, params, tokens)
    x, _ = _dec_stack(cfg, params, x, enc_out, mode="train")
    return _unembed(cfg, params, x)


def loss_fn(cfg: ArchConfig, params: Params, batch) -> jnp.ndarray:
    from repro.models.transformer import cross_entropy

    logits = forward(cfg, params, batch["tokens"], batch["frames"])
    return cross_entropy(logits, batch["labels"])


def prefill(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
            frames: jnp.ndarray):
    enc_out = encode(cfg, params, frames)
    x = _embed_dec(cfg, params, tokens)
    x, states = _dec_stack(cfg, params, x, enc_out, mode="prefill")
    return _unembed(cfg, params, x[:, -1:, :]), states, jnp.int32(tokens.shape[1])


def chunk_init(cfg: ArchConfig, params: Params, frames: jnp.ndarray, dtype):
    """Zero-token carry for chunked decoder prefill: run the encoder once
    and stack every layer's cross-KV up front (numerically the same
    per-layer einsum ``_dec_stack`` computes in-scan, batched over the
    layer axis); self-KV starts zero-length."""
    enc_out = encode(cfg, params, frames)
    wk = params["dec_layers"]["cross_attn"]["wk"]
    wv = params["dec_layers"]["cross_attn"]["wv"]
    ck = jnp.einsum("bsd,ldhk->lbshk", enc_out, wk.astype(enc_out.dtype))
    cv = jnp.einsum("bsd,ldhk->lbshk", enc_out, wv.astype(enc_out.dtype))
    kv = jnp.zeros((cfg.n_layers, frames.shape[0], 0, cfg.n_kv_heads,
                    cfg.head_dim_), dtype)
    return {"k": kv, "v": kv, "ck": ck, "cv": cv}


def prefill_chunk(cfg: ArchConfig, params: Params, states, tokens: jnp.ndarray,
                  start: jnp.ndarray):
    """One chunk of a chunked decoder prefill at absolute positions
    ``start .. start+s`` — returns (last-position logits, grown carry)."""
    x = _embed_dec(cfg, params, tokens, cur_index=start)
    x, new_states = _dec_stack(cfg, params, x, None, mode="chunk",
                               states=states)
    return _unembed(cfg, params, x[:, -1:, :]), new_states


def decode_step(cfg: ArchConfig, params: Params, states, cur_index, token,
                page_table=None, page_size: int = 0):
    x = _embed_dec(cfg, params, token, cur_index=cur_index)
    x, new_states = _dec_stack(cfg, params, x, None, mode="decode",
                               states=states, cur_index=cur_index,
                               page_table=page_table, page_size=page_size)
    return _unembed(cfg, params, x), new_states


def make_cache(cfg: ArchConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    kv = lambda s: jnp.zeros((cfg.n_layers, batch, s, cfg.n_kv_heads,
                              cfg.head_dim_), dtype)
    return {"k": kv(s_max), "v": kv(s_max), "ck": kv(cfg.enc_seq),
            "cv": kv(cfg.enc_seq)}
