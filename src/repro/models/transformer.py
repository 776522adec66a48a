"""Decoder-only LM covering dense / GQA / MoE / SSM / hybrid / VLM configs.

Layer stacking follows DESIGN.md §2/§8: the depth dimension is a
``lax.scan`` over superblocks (the distributed-scale echo of the paper's
feedback datapath — one reused layer "multiplier" instead of an unrolled
per-layer pipeline), with ``jax.checkpoint`` around the scanned body for
remat.  Heterogeneous stacks (Jamba) unroll the period *inside* the body.

States (decode caches) are stacked per superblock position with a leading
(n_groups, ...) axis.  Prefill emits them from the scan; decode carries
the stacks through it and updates each layer's index in place.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.layers import init as linit
from repro.layers.norms import norm_apply, norm_init
from repro.layers.rope import mrope_cos_sin, rope_cos_sin
from repro.models import blocks
from repro.runtime.sharding import constrain

Params = Dict[str, Any]


def init(cfg: ArchConfig, rng) -> Params:
    kinds = cfg.block_kinds()
    r = jax.random.split(rng, len(kinds) + 3)
    layers = {}
    for i, kind in enumerate(kinds):
        layers[f"pos{i}"] = linit.stacked(
            r[i], cfg.n_groups, lambda rr, kk=kind: blocks.block_init(rr, cfg, kk)
        )
    params: Params = {
        "embed": linit.trunc_normal(r[-3], (cfg.vocab, cfg.d_model), 0.02),
        "layers": layers,
        "final_norm": norm_init(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = linit.dense_init(
            r[-2], cfg.d_model, (cfg.d_model, cfg.vocab)
        )
    if cfg.pos == "learned":
        params["pos_embed"] = linit.trunc_normal(
            r[-1], (cfg.max_seq, cfg.d_model), 0.02
        )
    return params


def _rope_info(cfg: ArchConfig, batch: int, seq: int,
               pos_ids: Optional[jnp.ndarray],
               cur_index: Optional[jnp.ndarray] = None):
    """cos/sin for the whole stack (shared across layers).

    ``cur_index`` may be a scalar (lockstep decode) or a (b,) vector of
    per-slot positions (continuous batching).
    """
    if cfg.pos == "rope":
        if cur_index is not None:
            cur = jnp.asarray(cur_index, jnp.int32)
            if cur.ndim == 1:
                cur = cur[:, None]
            positions = jnp.full((batch, seq), 0, jnp.int32) + cur
        else:
            positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                         (batch, seq))
        return rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
    if cfg.pos == "mrope":
        assert pos_ids is not None, "mrope needs pos_ids (3, b, s)"
        return mrope_cos_sin(pos_ids, cfg.head_dim_, cfg.rope_theta,
                             cfg.mrope_sections)
    return None


@jax.named_scope("embed")
def embed_tokens(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
                 cur_index: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.pos == "learned":
        if cur_index is not None and jnp.ndim(cur_index) == 1:
            # per-slot positions: (b,) gather, decode seq is 1
            pe = jnp.take(params["pos_embed"], cur_index, axis=0)[:, None]
        elif cur_index is not None:
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], cur_index, tokens.shape[1], axis=0
            )[None]
        else:
            pe = params["pos_embed"][: tokens.shape[1]][None]
        x = x + pe.astype(cfg.dtype)
    return x


@jax.named_scope("lm_head")
def unembed(cfg: ArchConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = norm_apply(cfg.norm, params["final_norm"], x, eps=cfg.norm_eps,
                   policy=cfg.policy(), kernel_impl=cfg.kernel_impl)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h,
                            linit.cast(params["embed"], h.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h,
                            linit.cast(params["lm_head"], h.dtype))
    return constrain(logits, "dp", None, "model")


def _stack(cfg: ArchConfig, params: Params, x: jnp.ndarray, *, mode: str,
           rope_cs, states=None, cur_index=None, page_table=None,
           page_size: int = 0):
    """Scan the layer stack.  Returns (x, new_states or None).

    The stacked weights ride the scan as ``xs``.  Prefill and chunk emit
    each layer's states as ``ys``.  Decode carries the stacked states
    instead: each layer reads and writes its own index of the one stack
    in place, so no layer's slab is sliced out, written back, or copied
    into the donated output.
    """
    kinds = cfg.block_kinds()
    has_state = mode in ("prefill", "decode", "chunk")

    def apply(x, gparams, gstates, layer):
        new_gstates = {}
        for i, kind in enumerate(kinds):
            x, new_gstates[f"pos{i}"] = blocks.block_apply(
                cfg, kind, gparams[f"pos{i}"], x, mode=mode, rope_cs=rope_cs,
                state=None if gstates is None else gstates[f"pos{i}"],
                layer=layer, cur_index=cur_index, page_table=page_table,
                page_size=page_size,
            )
        return x, (new_gstates if has_state else None)

    if mode == "decode":
        def body(carry, group):
            return apply(carry[0], group[0], carry[1], group[1]), None

        init = (x, states)
        xs = (params["layers"], jnp.arange(cfg.n_groups, dtype=jnp.int32))
    else:
        def body(x, group):
            return apply(x, group[0], group[1], None)

        init = x
        xs = (params["layers"], states if mode == "chunk" else None)

    if cfg.scan_layers:
        fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
        # the scope names what the scan itself adds: each layer's slice
        # of the stacked weights (outside decode also of the states, and
        # the write of the new states into a fresh stack)
        with jax.named_scope("layer_scan"):
            out, ys = jax.lax.scan(fn, init, xs)
    else:
        out, outs = init, []
        for gi in range(cfg.n_groups):
            out, y = body(out, jax.tree.map(lambda a: a[gi], xs))
            outs.append(y)
        ys = jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
    return out if mode == "decode" else (out, ys)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def forward(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
            pos_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Training forward: tokens (b, s) -> logits (b, s, vocab)."""
    b, s = tokens.shape
    rope_cs = _rope_info(cfg, b, s, pos_ids)
    x = embed_tokens(cfg, params, tokens)
    if cfg.seq_parallel:
        x = constrain(x, "dp", "model", None)
    x, _ = _stack(cfg, params, x, mode="train", rope_cs=rope_cs)
    return unembed(cfg, params, x)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray]
            ) -> jnp.ndarray:
    """Mean next-token cross-entropy (log-domain: division-free)."""
    logits = forward(cfg, params, batch["tokens"], batch.get("pos_ids"))
    return cross_entropy(logits, batch["labels"])


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def make_cache(cfg: ArchConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Zeroed decode state, stacked (n_groups, ...) per superblock position."""
    kinds = cfg.block_kinds()
    cache = {}
    for i, kind in enumerate(kinds):
        one = blocks.init_block_state(cfg, kind, batch, s_max, dtype)
        cache[f"pos{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_groups,) + a.shape), one
        )
    return cache


def prefill(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
            pos_ids: Optional[jnp.ndarray] = None):
    """Prefill pass: returns (last-position logits, states, next_index).

    The emitted KV caches have length = prompt length; callers growing
    beyond it should allocate with make_cache and write through (serve.py).
    """
    b, s = tokens.shape
    rope_cs = _rope_info(cfg, b, s, pos_ids)
    x = embed_tokens(cfg, params, tokens)
    x, states = _stack(cfg, params, x, mode="prefill", rope_cs=rope_cs)
    logits = unembed(cfg, params, x[:, -1:, :])
    return logits, states, jnp.int32(s)


def chunk_init(cfg: ArchConfig, batch: int, dtype) -> Dict[str, Any]:
    """Zero-token carry for chunked prefill: zero-length KV leaves plus
    zeroed SSM states — exactly ``make_cache`` at ``s_max=0``."""
    return make_cache(cfg, batch, 0, dtype)


def prefill_chunk(cfg: ArchConfig, params: Params, states, tokens: jnp.ndarray,
                  start: jnp.ndarray, pos_ids: Optional[jnp.ndarray] = None):
    """One chunk of a chunked prefill: tokens (b, s) at absolute positions
    ``start .. start+s``, against the carry from the previous chunks.

    Returns (last-position logits (b, 1, V), grown carry).  The carry is
    ``chunk_init`` for the first chunk, or a resumed state rebuilt from
    shared prefix pages (serving/cache.py ``resume_state``).  Positions
    are built directly from ``start`` (a traced scalar) — ``_rope_info``'s
    scalar-cur path broadcasts ONE position over the sequence, which is
    decode semantics, not chunk semantics.
    """
    b, s = tokens.shape
    if cfg.pos == "rope":
        positions = start + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s))
        rope_cs = rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
    elif cfg.pos == "mrope":
        assert pos_ids is not None, "mrope chunk needs pos_ids (3, b, s)"
        rope_cs = mrope_cos_sin(pos_ids, cfg.head_dim_, cfg.rope_theta,
                                cfg.mrope_sections)
    else:
        rope_cs = None
    x = embed_tokens(cfg, params, tokens,
                     cur_index=start if cfg.pos == "learned" else None)
    x, new_states = _stack(cfg, params, x, mode="chunk", rope_cs=rope_cs,
                           states=states)
    logits = unembed(cfg, params, x[:, -1:, :])
    return logits, new_states


def decode_step(cfg: ArchConfig, params: Params, states, cur_index: jnp.ndarray,
                token: jnp.ndarray, pos_ids: Optional[jnp.ndarray] = None,
                page_table: Optional[jnp.ndarray] = None,
                page_size: int = 0):
    """One decode step: token (b, 1) -> (logits (b, 1, V), new states).

    ``cur_index`` is a scalar for lockstep batches or a (b,) vector of
    per-slot sequence positions (the serving engine's slot pool).  With
    ``page_table`` (b, pages_per_slot) the KV leaves of ``states`` are a
    shared page arena and decode reads/writes through the block table
    (serving/cache.py PagedCachePool); SSM/conv leaves stay slot-indexed.
    """
    b = token.shape[0]
    rope_cs = _rope_info(cfg, b, 1, pos_ids, cur_index=cur_index)
    x = embed_tokens(cfg, params, token,
                     cur_index=cur_index if cfg.pos == "learned" else None)
    x, new_states = _stack(cfg, params, x, mode="decode", rope_cs=rope_cs,
                           states=states, cur_index=cur_index,
                           page_table=page_table, page_size=page_size)
    logits = unembed(cfg, params, x)
    return logits, new_states
