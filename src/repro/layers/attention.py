"""GQA attention with a Goldschmidt softmax (division sites #1 and #3).

Three execution modes:

* ``flash_chunked`` — training/prefill: double-chunked online-softmax
  (lax.scan over q blocks, inner scan over kv blocks).  The recurrence is
  division-free (running max + unnormalized sum); the single normalization
  is a policy reciprocal at the end — the paper's "one reused multiplier"
  epilogue.  ``block_skip=True`` scans a static lower-triangle pair list
  instead of the full rectangle (causal FLOP halving, a §Perf change).

* ``flash_chunked`` with ``kernel_impl='pallas'`` — same arithmetic via the
  Pallas kernel (real-TPU path; interpret on CPU).

* ``decode`` — one new token vs a (b, S, kh, hd) KV cache, dense softmax
  over the masked cache with the policy softmax.  Under GSPMD the cache
  stays sharded (batch over 'data'; over 'model' the KV heads where they
  divide it, and each chip attends its own heads with no collective,
  else head_dim, whose contraction inserts one small psum per step).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.formats import kv_cast, kv_dequantize
from repro.core.policy import NumericsPolicy
from repro.layers import init as linit
from repro.runtime.sharding import constrain, kv_layout

NEG_INF = -1e30


def attn_init(rng, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int):
    r = jax.random.split(rng, 4)
    return {
        "wq": linit.dense_init(r[0], d_model, (d_model, n_heads, head_dim)),
        "wk": linit.dense_init(r[1], d_model, (d_model, n_kv_heads, head_dim)),
        "wv": linit.dense_init(r[2], d_model, (d_model, n_kv_heads, head_dim)),
        "wo": linit.dense_init(r[3], n_heads * head_dim, (n_heads, head_dim, d_model)),
    }


@jax.named_scope("attn_proj")
def qkv(params, x):
    """x (b,s,d) -> q (b,s,H,hd), k/v (b,s,KH,hd) in x.dtype."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, linit.cast(params["wq"], dt))
    k = jnp.einsum("bsd,dhk->bshk", x, linit.cast(params["wk"], dt))
    v = jnp.einsum("bsd,dhk->bshk", x, linit.cast(params["wv"], dt))
    return q, k, v


@jax.named_scope("attn_proj")
def out_proj(params, o):
    """o (b,s,H,hd) -> (b,s,d)."""
    return jnp.einsum("bshk,hkd->bsd", o, linit.cast(params["wo"], o.dtype))


# ---------------------------------------------------------------------------
# chunked flash (train / prefill)
# ---------------------------------------------------------------------------


def _block_pairs(n_q: int, n_kv: int, q_block: int, kv_block: int):
    """Static causal lower-triangle block pair list (iq, ik)."""
    pairs = []
    for iq in range(n_q):
        hi = iq * q_block + q_block - 1  # last query row in block
        for ik in range(n_kv):
            if ik * kv_block <= hi:
                pairs.append((iq, ik))
    return pairs


def expand_kv_heads(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(b, s, KH, hd) -> (b, s, H, hd) via a head-axis gather.

    GQA without the (KH, group) reshape: reshaping a 'model'-sharded H axis
    into (KH, g) factors breaks GSPMD propagation (KH < mesh axis) and
    silently replicates attention over 'model' (measured: 8.4x device
    FLOPs on the first dry-run).  A static gather keeps one whole H axis:
    the output shards on H, and XLA fuses the duplication into the
    consumer matmul.
    """
    kh = k.shape[2]
    group = n_heads // kh
    idx = jnp.arange(n_heads, dtype=jnp.int32) // group
    return jnp.take(k, idx, axis=2)


def flash_chunked(
    q: jnp.ndarray,  # (b, sq, H, hd)
    k: jnp.ndarray,  # (b, sk, KH, hd)
    v: jnp.ndarray,
    *,
    policy: NumericsPolicy,
    causal: bool = True,
    q_block: int = 512,
    kv_block: int = 1024,
    block_skip: bool = False,
    seq_shard: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if causal:
        assert sq == sk, "causal flash assumes aligned self-attention"
    q_block = _pick_block(sq, q_block)
    kv_block = _pick_block(sk, kv_block)
    n_q, n_kv = sq // q_block, sk // kv_block

    kf = expand_kv_heads(k, h)
    vf = expand_kv_heads(v, h)
    # head-major layouts for clean contractions; H stays whole (sharded).
    # The explicit constraints pin the 'model' sharding of H through the
    # nested scan bodies (GSPMD propagation drops it — see sharding.py).
    qg = constrain(q.transpose(0, 2, 3, 1) * sm_scale, "dp", "model", None, None)
    kT = constrain(kf.transpose(0, 2, 3, 1), "dp", "model", None, None)
    vT = constrain(vf.transpose(0, 2, 1, 3), "dp", "model", None, None)

    h_ax = None if seq_shard else "model"

    def kv_step(qb, carry, ik, row0):
        """qb (b,H,bq) x hd already sliced; row0 = absolute first q row."""
        acc, m, l = carry  # acc (b,H,bq,hd); m,l (b,H,bq,1)
        kb = jax.lax.dynamic_slice_in_dim(kT, ik * kv_block, kv_block, axis=3)
        vb = jax.lax.dynamic_slice_in_dim(vT, ik * kv_block, kv_block, axis=2)
        sblk = jnp.einsum(
            "bhdq,bhdt->bhqt", qb.astype(jnp.float32), kb.astype(jnp.float32)
        )  # (b,H,bq,bkv)
        if causal:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, sblk.shape, 2)
            cols = ik * kv_block + jax.lax.broadcasted_iota(jnp.int32, sblk.shape, 3)
            sblk = jnp.where(rows >= cols, sblk, NEG_INF)
        sblk = constrain(sblk, "dp", h_ax, None, None)
        m_cur = jnp.max(sblk, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(sblk - m_new)
        l_new = l * alpha + jnp.sum(e, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqt,bhtd->bhqd", e, vb.astype(jnp.float32))
        acc_new = constrain(acc_new, "dp", h_ax, None, None)
        return acc_new, m_new, l_new

    def q_block_out(qb, iq):
        """One q block -> NORMALIZED bf16 output (b,H,bq,hd).

        The Goldschmidt reciprocal epilogue runs per block so only the
        narrow output leaves the loop — no stacked f32 accumulators
        (§Perf iteration C1: the stacked (nq,b,H,bq,hd) f32 accumulator
        was the dominant memory-term item)."""
        acc0 = constrain(jnp.zeros((b, h, q_block, hd), jnp.float32),
                         "dp", h_ax, None, None)
        m0 = constrain(jnp.full((b, h, q_block, 1), NEG_INF, jnp.float32),
                       "dp", h_ax, None, None)
        l0 = constrain(jnp.zeros((b, h, q_block, 1), jnp.float32),
                       "dp", h_ax, None, None)

        def body(carry, ik):
            return kv_step(qb, carry, ik, iq * q_block), None

        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(n_kv))
        out = acc * policy.reciprocal(jnp.maximum(l, 1e-30))
        return out.astype(q.dtype)

    if block_skip and causal:
        # static triangle pair list; full-length accumulators, one pass.
        pairs = _block_pairs(n_q, n_kv, q_block, kv_block)
        acc0 = constrain(jnp.zeros((b, h, sq, hd), jnp.float32),
                         "dp", "model", None, None)
        m0 = constrain(jnp.full((b, h, sq, 1), NEG_INF, jnp.float32),
                       "dp", "model", None, None)
        l0 = constrain(jnp.zeros((b, h, sq, 1), jnp.float32),
                       "dp", "model", None, None)

        def pair_body(carry, pair):
            acc, m, l = carry
            iq, ik = pair[0], pair[1]
            qb = jax.lax.dynamic_slice_in_dim(qg, iq * q_block, q_block, 3)
            a_blk = jax.lax.dynamic_slice_in_dim(acc, iq * q_block, q_block, 2)
            m_blk = jax.lax.dynamic_slice_in_dim(m, iq * q_block, q_block, 2)
            l_blk = jax.lax.dynamic_slice_in_dim(l, iq * q_block, q_block, 2)
            a2, m2, l2 = kv_step(qb, (a_blk, m_blk, l_blk), ik,
                                 iq * q_block)
            acc = jax.lax.dynamic_update_slice_in_dim(acc, a2, iq * q_block, 2)
            m = jax.lax.dynamic_update_slice_in_dim(m, m2, iq * q_block, 2)
            l = jax.lax.dynamic_update_slice_in_dim(l, l2, iq * q_block, 2)
            return (acc, m, l), None

        (acc, _, l), _ = jax.lax.scan(
            pair_body, (acc0, m0, l0), jnp.asarray(pairs, jnp.int32)
        )
        out = acc * policy.reciprocal(jnp.maximum(l, 1e-30))
        out = out.astype(q.dtype)
    else:
        # q blocks become a leading axis.  seq_shard=True shards that axis
        # over 'model' and runs the blocks in PARALLEL (vmap) — sequence-
        # parallel attention for archs whose head count doesn't divide the
        # TP axis (minicpm 36H, whisper 20H; §Perf iteration A).  The
        # default serial map is one reused datapath per block — the
        # paper's feedback idea at the attention level.
        qblocks = jnp.moveaxis(
            qg.reshape(b, h, hd, n_q, q_block), 3, 0)  # (nq,b,h,hd,bq)
        if seq_shard:
            qblocks = constrain(qblocks, "model", "dp", None, None, None)
            outs = jax.vmap(q_block_out)(qblocks, jnp.arange(n_q))
            outs = constrain(outs, "model", "dp", None, None, None)
        else:
            outs = jax.lax.map(lambda args: q_block_out(*args),
                               (qblocks, jnp.arange(n_q)))
        out = jnp.moveaxis(outs, 0, 2).reshape(b, h, sq, hd)

    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _pick_block(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (handles s=1500 etc.)."""
    from repro.kernels.common import fit_block  # lazy: keep layers light

    return fit_block(s, target)


def flash(
    q: jnp.ndarray,  # (b, s, H, hd)
    k: jnp.ndarray,  # (b, s, KH, hd)
    v: jnp.ndarray,
    *,
    policy: NumericsPolicy,
    causal: bool = True,
    kernel_impl: str = "jnp",
    q_block: int = 512,
    kv_block: int = 1024,
    block_skip: bool = False,
    seq_shard: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Train/prefill attention front-end: fused Pallas kernel or chunked jnp.

    ``kernel_impl='pallas'`` routes through :mod:`repro.kernels.ops`, whose
    dispatch fills block_q/block_kv (and the interpret path) from the
    autotune cache when tuning is enabled; the policy pins the Goldschmidt
    variant and iteration count either way.
    """
    if kernel_impl == "pallas":
        from repro.kernels import ops

        o = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, sm_scale=sm_scale,
            variant=policy.variant, **policy.kernel_precision(q.dtype),
        )
        return o.transpose(0, 2, 1, 3)
    return flash_chunked(
        q, k, v, policy=policy, causal=causal, q_block=q_block,
        kv_block=kv_block, block_skip=block_skip, seq_shard=seq_shard,
        sm_scale=sm_scale,
    )


def attention_dense(
    q, k, v, *, policy: NumericsPolicy, causal: bool,
    sm_scale: Optional[float] = None,
):
    """Unchunked reference path (small seqs / cross-attention).

    q (b,sq,H,hd), k/v (b,sk,KH,hd).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    kf = expand_kv_heads(k, h)
    vf = expand_kv_heads(v, h)
    logits = jnp.einsum(
        "bqhd,bthd->bhqt", q.astype(jnp.float32), kf.astype(jnp.float32)
    ) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = policy.softmax(logits, axis=-1)
    o = jnp.einsum("bhqt,bthd->bqhd", probs, vf.astype(jnp.float32))
    return o.astype(q.dtype)


def chunk_attention(
    q, k_all, v_all, *, policy: NumericsPolicy,
    sm_scale: Optional[float] = None,
):
    """Chunked-prefill attention: ``sq`` new query rows against the full
    KV prefix so far.

    ``k_all``/``v_all`` (b, base+sq, KH, hd) hold every position up to
    the end of this chunk; the queries are the last ``sq`` of them.  The
    causal rule is ``col <= base + iq``, which is exactly
    :func:`attention_dense`'s ``tril(..., k=sk-sq)`` mask — so this is a
    thin delegate.  What it buys: one compiled artifact (and one
    arithmetic schedule) per (prefix length, chunk length) pair,
    independent of the *total* prompt length — the property that makes a
    prefill resumed from a shared page boundary bit-exact against a cold
    chunked prefill of the same prompt (serving/cache.py, prefix
    sharing).
    """
    return attention_dense(q, k_all, v_all, policy=policy, causal=True,
                           sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# decode (one token against a cache)
# ---------------------------------------------------------------------------


@jax.named_scope("decode_attention")
def decode_attention(
    q: jnp.ndarray,        # (b, 1, H, hd)
    k_cache: jnp.ndarray,  # (b, S, KH, hd)
    v_cache: jnp.ndarray,
    cur_index: jnp.ndarray,  # int32 scalar or (b,): valid cache slots - 1
    *,
    policy: NumericsPolicy,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    b, _, h, hd = q.shape
    S, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    kv_h, kv_d = kv_layout(kh)
    # move q from the projection's head sharding onto the cache layout
    # before the contraction.  KV heads over 'model': the query's
    # contiguous head shards already match.  head_dim over 'model':
    # resharding the (b, 1, h, hd) query is one tiny collective; letting
    # GSPMD align the batch-dim kh instead reshards the whole KV cache
    # every tick
    qg = constrain(q.reshape(b, kh, g, hd), "dp", kv_h, None, kv_d)
    # kv_dequantize: plain f32 cast for float caches; int8 arenas (the
    # quantized serving path) scale back by the static KV step
    logits = jnp.einsum(
        "bkgd,btkd->bkgt", qg.astype(jnp.float32), kv_dequantize(k_cache)
    ) * sm_scale  # (b, kh, g, S)
    # a contraction over a 'model'-sharded head_dim: pin the result
    # replicated over 'model' so GSPMD lowers the intended small psum
    # instead of resharding the (much larger) KV cache around the einsum
    logits = constrain(logits, "dp", kv_h, None, None)
    pos = jnp.arange(S)[None, None, None, :]
    cur = jnp.asarray(cur_index)
    if cur.ndim == 1:  # per-slot sequence lengths (continuous batching)
        cur = cur[:, None, None, None]
    logits = jnp.where(pos <= cur, logits, NEG_INF)
    probs = policy.softmax(logits, axis=-1)
    # masked probs underflow to exact fp32 zeros, but 0 * NaN is still
    # NaN in the V contraction: select the masked V rows to zero so a
    # stale row beyond cur (e.g. the one NaN KV write a quarantined slot
    # leaves behind — serving/resilience.py) can never contaminate the
    # next occupant of a recycled slot or page.  Bit-identical for
    # finite stale rows (their prob is exactly 0 either way).
    with jax.named_scope("kv_mask"):
        vmask = jnp.arange(S)[None, :, None, None] <= jnp.reshape(
            cur, (-1, 1, 1, 1) if cur.ndim else ())
        v_masked = jnp.where(vmask, kv_dequantize(v_cache), 0.0)
    o = jnp.einsum("bkgt,btkd->bkgd", probs, v_masked)
    o = constrain(o, "dp", kv_h, None, kv_d)  # back on the cache layout
    return o.reshape(b, 1, h, hd).astype(q.dtype)


@jax.named_scope("kv_write")
def cache_update(
    k_stack: jnp.ndarray, v_stack: jnp.ndarray, layer,
    k_new: jnp.ndarray, v_new: jnp.ndarray, cur_index: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Insert (b, 1, KH, hd) new K/V into layer ``layer`` of the stacked
    (L, b, S, KH, hd) caches at row ``cur_index``, in place: one row per
    slot is written and no layer slab is materialized.

    ``cur_index`` may be a scalar (lockstep batch) or a (b,) vector of
    per-slot write positions (continuous batching).  Out-of-range rows
    clamp, as ``dynamic_update_slice`` does.
    """
    cur = jnp.asarray(cur_index)
    # kv_cast = astype for float caches, round-to-scale for int8 arenas
    if cur.ndim == 1:
        at = (layer, jnp.arange(k_new.shape[0]), cur)
        return (k_stack.at[at].set(kv_cast(k_new[:, 0], k_stack.dtype),
                                   mode="clip"),
                v_stack.at[at].set(kv_cast(v_new[:, 0], v_stack.dtype),
                                   mode="clip"))
    start = (layer, 0, cur, 0, 0)
    return (jax.lax.dynamic_update_slice(
                k_stack, kv_cast(k_new, k_stack.dtype)[None], start),
            jax.lax.dynamic_update_slice(
                v_stack, kv_cast(v_new, v_stack.dtype)[None], start))


# ---------------------------------------------------------------------------
# paged decode (block-table cache: a shared page arena instead of rows)
# ---------------------------------------------------------------------------
#
# The paged pool (serving/cache.py) replaces per-slot max-length rows with
# a (L, n_pages, page_size, KH, hd) arena per KV leaf; each slot owns a
# block-table row of page ids.  Decode resolves the indirection inside
# the fused tick, straight on the stacked arena at the layer's index:
# ``paged_cache_update`` scatters the new K/V at (layer, page, offset)
# derived from cur_index, ``gather_pages`` materializes the slot's dense
# view for the unchanged ``decode_attention``.  Parity with the dense
# path is exact: positions beyond cur_index gather recycled-page garbage,
# but the ``pos <= cur`` mask sends them to NEG_INF and
# ``exp(NEG_INF - m)`` underflows to fp32 zero, so softmax sums (and the
# prob-weighted V contraction, 0 * finite = 0) are bit-identical to the
# zero-padded dense rows.  Page id 0 is the pool's trash page: freed
# slots keep all-zero table rows and cur = 0, so their stale tick writes
# land there.


@jax.named_scope("kv_write")
def paged_cache_update(
    k_arena: jnp.ndarray,  # (L, P, page_size, KH, hd)
    v_arena: jnp.ndarray,
    layer,                 # int32 scalar layer index
    k_new: jnp.ndarray,    # (b, 1, KH, hd)
    v_new: jnp.ndarray,
    page_table: jnp.ndarray,  # (b, pages_per_slot) int32 page ids
    cur_index: jnp.ndarray,   # (b,) write positions
    page_size: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter the new K/V of every slot through its block table into
    layer ``layer`` of the arena, in place."""
    cur = jnp.asarray(cur_index)
    pid = jnp.take_along_axis(
        page_table, (cur // page_size)[:, None], axis=1)[:, 0]  # (b,)
    off = cur % page_size
    return (k_arena.at[layer, pid, off].set(kv_cast(k_new[:, 0],
                                                    k_arena.dtype)),
            v_arena.at[layer, pid, off].set(kv_cast(v_new[:, 0],
                                                    v_arena.dtype)))


@jax.named_scope("gather_pages")
def gather_pages(arena: jnp.ndarray, layer,
                 page_table: jnp.ndarray) -> jnp.ndarray:
    """(L, P, page_size, KH, hd) arena, layer index and (b, n) block
    table -> dense (b, n*ps, KH, hd) per-slot view for
    ``decode_attention``, gathered straight from the stack.  An
    out-of-range page id reads the fill value, as ``jnp.take`` does."""
    pages = arena.at[layer, page_table].get(mode="fill")  # (b, n, ps, KH, hd)
    b, n, ps = pages.shape[:3]
    return pages.reshape(b, n * ps, *pages.shape[3:])
