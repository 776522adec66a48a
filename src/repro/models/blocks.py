"""One residual block = (norm -> mixer -> +res) [-> norm -> ffn -> +res].

``kind = (mixer, ffn)`` with mixer in {attn, mamba} and ffn in
{mlp, moe, none}; the per-arch pattern comes from ``ArchConfig.block_kinds``.
All blocks run in one of four modes:

  train   — full sequence, no state I/O
  prefill — full sequence, emits decode state (KV cache / SSM state)
  chunk   — one prompt chunk, consumes + emits a growing prefill carry
            (KV concatenated, SSM states threaded) — the chunked-prefill
            path whose arithmetic schedule is independent of the total
            prompt length (serving prefix-sharing resume)
  decode  — one token, consumes + emits state

In prefill and chunk the state leaves carry NO layer axis; the model
stacks them.  In decode the model passes the whole stacked state (leading
layer axis) with the layer's index, and the block reads and writes its
own layer of the stack in place.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.layers import attention as attn
from repro.layers import mamba as mb
from repro.layers import mlp as mlp_mod
from repro.layers import moe as moe_mod
from repro.layers.norms import norm_apply, norm_init
from repro.layers.rope import apply_rope
from repro.runtime.sharding import constrain


def block_init(rng, cfg: ArchConfig, kind: Tuple[str, str]) -> Dict[str, Any]:
    mixer, ffn = kind
    r = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"norm1": norm_init(cfg.norm, cfg.d_model)}
    if mixer == "attn":
        p["attn"] = attn.attn_init(
            r[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
        )
    else:
        p["mamba"] = mb.mamba_init(
            r[0], cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.d_conv, cfg.dt_rank_
        )
    if ffn != "none":
        p["norm2"] = norm_init(cfg.norm, cfg.d_model)
        if ffn == "moe":
            p["moe"] = moe_mod.moe_init(r[1], cfg.d_model, cfg.d_ff, cfg.n_experts,
                                        cfg.act)
        else:
            p["mlp"] = mlp_mod.mlp_init(r[1], cfg.d_model, cfg.d_ff, cfg.act)
    return p


def init_block_state(cfg: ArchConfig, kind: Tuple[str, str], batch: int,
                     s_max: int, dtype) -> Dict[str, jnp.ndarray]:
    """Zeroed decode state for one layer of this kind."""
    mixer, _ = kind
    if mixer == "attn":
        shape = (batch, s_max, cfg.n_kv_heads, cfg.head_dim_)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }


def _residual(cfg: ArchConfig, x, out):
    if cfg.scale_depth:
        out = out * (cfg.scale_depth / (cfg.n_layers ** 0.5))
    return x + out.astype(x.dtype)


def block_apply(
    cfg: ArchConfig,
    kind: Tuple[str, str],
    params: Dict[str, Any],
    x: jnp.ndarray,  # (b, s, d)
    *,
    mode: str,  # train | prefill | decode
    rope_cs: Optional[Tuple[jnp.ndarray, jnp.ndarray]],  # cos/sin (b,s,hd/2)
    state: Optional[Dict[str, jnp.ndarray]] = None,
    layer=None,  # decode: this layer's index into the stacked state
    cur_index: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,  # (b, pages) paged decode
    page_size: int = 0,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    mixer, ffn = kind
    policy = cfg.policy()
    # full sequence parallelism: seq dim of the residual stream (and of
    # q/k/v) sharded over 'model'; otherwise heads carry the TP axis.
    # chunk mode runs page-sized batch-1 slices — too short to shard.
    sp = cfg.seq_parallel and mode not in ("decode", "chunk")
    s_ax = "model" if sp else None
    h_ax = None if sp else "model"
    h = norm_apply(cfg.norm, params["norm1"], x, eps=cfg.norm_eps, policy=policy,
                   kernel_impl=cfg.kernel_impl)
    new_state: Optional[Dict[str, jnp.ndarray]] = None

    if mixer == "attn":
        q, k, v = attn.qkv(params["attn"], h)
        q = constrain(q, "dp", s_ax, h_ax, None)
        k = constrain(k, "dp", s_ax, None, None)
        v = constrain(v, "dp", s_ax, None, None)
        if rope_cs is not None:
            cos, sin = rope_cs
            # re-pin after rope: its rotate-half concatenate must never be
            # partitioned along head_dim (XLA SPMD miscompiles a concat
            # whose seam lands on a shard boundary — same bug class as
            # encdec._sinusoid), and GSPMD would otherwise pick the
            # decode cache's hd-sharded layout for it
            q = constrain(apply_rope(q, cos, sin), "dp", s_ax, h_ax, None)
            k = constrain(apply_rope(k, cos, sin), "dp", s_ax, None, None)
        if mode == "decode":
            assert state is not None and cur_index is not None
            # the constrain templates keep the stacks on the pool's
            # placement (layer axis unsharded, slots or pages over dp,
            # head_dim over 'model') so the sharded cache round-trips the
            # tick without rematerialization: the scatter of the new rows
            # drops the sharding under GSPMD otherwise
            if page_table is not None:
                # block-table path: KV leaves are the shared page arena
                # (L, n_pages, page_size, KH, hd); scatter through the
                # table, then gather the slot's dense view for the same
                # decode_attention (bit-exact vs the row path — see
                # attention.py "paged decode").  The arena's page axis
                # sits where the slot axis was (pool_shardings rules).
                kc, vc = attn.paged_cache_update(
                    state["k"], state["v"], layer, k, v, page_table,
                    cur_index, page_size)
                kc = constrain(kc, None, "dp", None, None, "model")
                vc = constrain(vc, None, "dp", None, None, "model")
                kv = constrain(attn.gather_pages(kc, layer, page_table),
                               "dp", None, None, "model")
                vv = constrain(attn.gather_pages(vc, layer, page_table),
                               "dp", None, None, "model")
            else:
                kc, vc = attn.cache_update(
                    state["k"], state["v"], layer, k, v, cur_index)
                kc = constrain(kc, None, "dp", None, None, "model")
                vc = constrain(vc, None, "dp", None, None, "model")
                kv, vv = kc[layer], vc[layer]
            o = attn.decode_attention(q, kv, vv, cur_index, policy=policy)
            new_state = {"k": kc, "v": vc}
        elif mode == "chunk":
            # chunked prefill: the carry holds the KV of every earlier
            # chunk; append this chunk's and attend the new rows against
            # the whole prefix (attention.chunk_attention — one schedule
            # per (prefix, chunk) pair, total-length independent)
            assert state is not None
            k_all = jnp.concatenate([state["k"], k], axis=1)
            v_all = jnp.concatenate([state["v"], v], axis=1)
            o = attn.chunk_attention(q, k_all, v_all, policy=policy)
            new_state = {"k": k_all, "v": v_all}
        else:
            o = attn.flash(
                q, k, v, policy=policy, causal=True,
                kernel_impl=cfg.kernel_impl,
                q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block,
                block_skip=cfg.attn_block_skip,
                seq_shard=cfg.attn_seq_shard,
            )
            if mode == "prefill":
                new_state = {"k": k, "v": v}
        out = attn.out_proj(params["attn"], o)
    else:  # mamba
        if mode == "decode":
            assert state is not None
            out, conv_s, ssm_s = mb.mamba_decode_step(
                params["mamba"], h, state["conv"][layer], state["ssm"][layer],
                d_inner=cfg.d_inner, d_state=cfg.ssm_state, dt_rank=cfg.dt_rank_,
            )
            # written back at this layer's index of the stack; same
            # re-pin as the KV path: keep the SSM/conv states on the
            # decode-cache placement (d_inner over 'model') tick to tick
            conv_s = jax.lax.dynamic_update_index_in_dim(
                state["conv"], conv_s, layer, 0)
            ssm_s = jax.lax.dynamic_update_index_in_dim(
                state["ssm"], ssm_s, layer, 0)
            new_state = {"conv": constrain(conv_s, None, "dp", None, "model"),
                         "ssm": constrain(ssm_s, None, "dp", "model", None)}
        elif mode == "prefill":
            out, (conv_s, ssm_s) = mb.mamba_apply(
                params["mamba"], h, d_inner=cfg.d_inner, d_state=cfg.ssm_state,
                dt_rank=cfg.dt_rank_, chunk=cfg.mamba_chunk, return_state=True,
            )
            new_state = {"conv": conv_s, "ssm": ssm_s}
        elif mode == "chunk":
            # the SSM recurrence resumes exactly from the carried states;
            # the inner scan chunk is a divisor of the (fixed) chunk
            # length, so the schedule is total-length independent too
            assert state is not None
            out, (conv_s, ssm_s) = mb.mamba_apply(
                params["mamba"], h, d_inner=cfg.d_inner, d_state=cfg.ssm_state,
                dt_rank=cfg.dt_rank_, chunk=cfg.mamba_chunk,
                conv_state=state["conv"], ssm_state=state["ssm"],
                return_state=True,
            )
            new_state = {"conv": conv_s, "ssm": ssm_s}
        else:
            out = mb.mamba_apply(
                params["mamba"], h, d_inner=cfg.d_inner, d_state=cfg.ssm_state,
                dt_rank=cfg.dt_rank_, chunk=cfg.mamba_chunk,
            )
    x = constrain(_residual(cfg, x, out), "dp", s_ax, None)

    if ffn != "none":
        h = norm_apply(cfg.norm, params["norm2"], x, eps=cfg.norm_eps,
                       policy=policy, kernel_impl=cfg.kernel_impl)
        if ffn == "moe":
            out = moe_mod.moe_apply(
                params["moe"], h, n_experts=cfg.n_experts, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                group_size=cfg.moe_group_size,
                chunk_groups=cfg.moe_chunk_groups, policy=policy, act=cfg.act,
            )
        else:
            out = mlp_mod.mlp_apply(params["mlp"], h, act=cfg.act)
        x = constrain(_residual(cfg, x, out), "dp", s_ax, None)
    return x, new_state
