"""Blocked online-softmax (flash) attention with a Goldschmidt epilogue.

Division site #3 of DESIGN.md §3.  The online-softmax recurrence is kept
division-free (running max + running *unnormalized* sum); the single
normalization ``acc / l`` is deferred to the last KV block and computed by
the paper's Goldschmidt datapath on the (block_q, 1) denominator column —
the "one reused multiplier" epilogue instead of a divide per KV block.
This is itself the paper's insight applied at the kernel level: the
rescale multiplications are the reused MULT X/Y; the final reciprocal is
one Goldschmidt pass rather than `bq * n_kv` hardware divides.

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv axis innermost
("arbitrary" semantics — it carries the accumulator).  GQA is expressed in
the k/v BlockSpec index_map (head -> head // group), so KV tiles are
fetched once per group without materializing repeated heads.

VMEM per step (f32): q/k/v/o tiles (bq+2*bkv+bq)*D + logits bq*bkv
~= (128+256+128)*128*4B + 128*128*4B ≈ 320 KB — comfortably sub-VMEM;
the MXU sees (bq, D) x (D, bkv) and (bq, bkv) x (bkv, D) contractions.

Backward (``custom_vjp``): the differentiated forward additionally emits
the per-row softmax statistics ``m`` (running max) and ``l``
(unnormalized denominator sum) as (B, H, S) outputs and saves
``(q, k, v, out, m, l)`` — the standard flash-attention saved-residual
scheme (out + logsumexp, here kept as the (m, l) pair so the backward
re-runs the *Goldschmidt* reciprocal of ``l`` instead of an exp of a
fused logsumexp).  Two backward Pallas kernels recompute the probability
tiles ``p = exp(s - m) · (1/l)`` blockwise and accumulate

    dv_j = Σ_i p_ij · do_i
    ds_ij = p_ij ⊙ (do_i·v_j - Δ_i),   Δ_i = Σ_d do_id·out_id
    dq_i = sm_scale · Σ_j ds_ij · k_j
    dk_j = sm_scale · Σ_i ds_ij · q_i

— a dq kernel (grid b, h, q_blocks, kv_blocks; kv innermost) and a dk/dv
pair kernel (grid b, h, kv_blocks, q_blocks; q innermost).  For GQA the
pair kernel produces per-q-head dk/dv which are group-summed to the KV
heads outside the kernel.  Backward block shapes resolve through the
tuning dispatch under the ``flash_attention_bwd`` registry entry.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1e30


def _mm(a, b, contract, operand_dtype):
    """Tile matmul with float32 accumulation.  Float32 operands take the
    MXU's multi-pass path so their products stay float32 (one pass would
    round them to bf16); bf16 operands are exact in one pass."""
    prec = (jax.lax.Precision.HIGHEST if operand_dtype == jnp.float32
            else None)
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, tab_ref, o_ref, *rest, sm_scale, causal,
            block_q, block_kv, n_kv_blocks, p, iters, variant,
            save_residuals):
    if save_residuals:
        m_out, l_out, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        s = _mm(q, k, ((1,), (1,)), q_ref.dtype) * sm_scale  # (bq, bkv)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            cols = ik * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # rescale of the old accumulator
        e = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(e, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _mm(e, v, ((1,), (0,)),
                                                  q_ref.dtype)
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # Skip fully-masked blocks (above the diagonal).
        @pl.when(ik * block_kv <= iq * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ik == n_kv_blocks - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-30)  # guard: fully-masked row
        inv = common.recip_positive(
            l, tab_ref[...], p=p, iters=iters, variant=variant
        )
        o_ref[0, 0] = (acc_ref[...] * inv).astype(o_ref.dtype)
        if save_residuals:
            m_out[0, 0] = m_ref[...]
            l_out[0, 0] = l_ref[...]


def _fwd_call(q, k, v, causal, sm_scale, block_q, block_kv, p, iters,
              variant, interpret, save_residuals):
    b, h, s, d = q.shape
    kh = k.shape[1]
    group = h // kh
    n_q = s // block_q
    n_kv = s // block_kv
    table = common.rom_table(p)
    out_shape = [jax.ShapeDtypeStruct((b, h, s, d), q.dtype)]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    ]
    if save_residuals:
        # per-row statistics keep a unit lane axis, (b, h, s, 1): a
        # (block_q, 1) column tiles on the chip, a bare (block_q,) row of
        # a (b, h, s) array does not
        for _ in range(2):  # m, l
            out_shape.append(jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32))
            out_specs.append(
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda ib, ih, iq, ik: (ib, ih, iq, 0))
            )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            sm_scale=sm_scale,
            causal=causal,
            block_q=block_q,
            block_kv=block_kv,
            n_kv_blocks=n_kv,
            p=p,
            iters=iters,
            variant=variant,
            save_residuals=save_residuals,
        ),
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda ib, ih, iq, ik, grp=group: (ib, ih // grp, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda ib, ih, iq, ik, grp=group: (ib, ih // grp, ik, 0),
            ),
            pl.BlockSpec((1 << p, 1), lambda ib, ih, iq, ik: (0, 0)),
        ],
        out_specs=out_specs if save_residuals else out_specs[0],
        out_shape=out_shape if save_residuals else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=common.interpret_flag(interpret),
    )(q, k, v, table)
    return out if save_residuals else (out, None, None)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _p_tile(q_ref, k_ref, m_ref, l_ref, tab_ref, *, iq, ik, sm_scale, causal,
            block_q, block_kv, p, iters, variant):
    """Recompute the (bq, bkv) probability tile from saved (m, l)."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    s = _mm(q, k, ((1,), (1,)), q_ref.dtype) * sm_scale
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    m = m_ref[0, 0]  # (bq, 1)
    l = jnp.maximum(l_ref[0, 0], 1e-30)
    inv = common.recip_positive(
        l, tab_ref[...], p=p, iters=iters, variant=variant
    )  # Goldschmidt pass on the saved denominator — same datapath as fwd
    return jnp.exp(s - m) * inv


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                   tab_ref, dq_ref, acc_ref, *, sm_scale, causal, block_q,
                   block_kv, n_kv_blocks, p, iters, variant):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        pt = _p_tile(q_ref, k_ref, m_ref, l_ref, tab_ref, iq=iq, ik=ik,
                     sm_scale=sm_scale, causal=causal, block_q=block_q,
                     block_kv=block_kv, p=p, iters=iters, variant=variant)
        do = do_ref[0, 0].astype(jnp.float32)  # (bq, D)
        v = v_ref[0, 0].astype(jnp.float32)    # (bkv, D)
        k = k_ref[0, 0].astype(jnp.float32)
        dp = _mm(do, v, ((1,), (1,)), q_ref.dtype)  # (bq, bkv)
        delta = delta_ref[0, 0]  # (bq, 1)
        ds = pt * (dp - delta) * sm_scale
        acc_ref[...] += _mm(ds, k, ((1,), (0,)), q_ref.dtype)

    if causal:
        @pl.when(ik * block_kv <= iq * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ik == n_kv_blocks - 1)
    def _write():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                    tab_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale,
                    causal, block_q, block_kv, n_q_blocks, p, iters, variant):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute():
        pt = _p_tile(q_ref, k_ref, m_ref, l_ref, tab_ref, iq=iq, ik=ik,
                     sm_scale=sm_scale, causal=causal, block_q=block_q,
                     block_kv=block_kv, p=p, iters=iters, variant=variant)
        q = q_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        dv_acc[...] += _mm(pt, do, ((0,), (0,)), q_ref.dtype)  # (bkv, D)
        dp = _mm(do, v, ((1,), (1,)), q_ref.dtype)
        delta = delta_ref[0, 0]
        ds = pt * (dp - delta) * sm_scale
        dk_acc[...] += _mm(ds, q, ((0,), (0,)), q_ref.dtype)  # (bkv, D)

    if causal:
        # Block is fully masked iff every row index < every col index.
        @pl.when(iq * block_q + block_q - 1 >= ik * block_kv)
        def _():
            compute()
    else:
        compute()

    @pl.when(iq == n_q_blocks - 1)
    def _write():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, do, out, m, l, *, causal, sm_scale, block_q, block_kv,
              p, iters, variant, interpret):
    """Run both backward kernels; returns (dq, dk, dv) at q/k/v shapes."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    group = h // kh
    n_q = s // block_q
    n_kv = s // block_kv
    table = common.rom_table(p)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # (b, h, s, 1), laid out like the saved m and l

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_kv, d),
        lambda ib, ih, iq, ik, grp=group: (ib, ih // grp, ik, 0),
    )
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    tab_spec = pl.BlockSpec((1 << p, 1), lambda ib, ih, iq, ik: (0, 0))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_kv=block_kv, n_kv_blocks=n_kv, p=p, iters=iters,
            variant=variant,
        ),
        grid=(b, h, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  row_spec, tab_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=common.interpret_flag(interpret),
    )(q, k, v, do, m, l, delta, table)

    # dk/dv: grid transposed (kv outer, q inner); per-q-head outputs.
    qT_spec = pl.BlockSpec((1, 1, block_q, d),
                           lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    kvT_spec = pl.BlockSpec(
        (1, 1, block_kv, d),
        lambda ib, ih, ik, iq, grp=group: (ib, ih // grp, ik, 0),
    )
    rowT_spec = pl.BlockSpec((1, 1, block_q, 1),
                             lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    tabT_spec = pl.BlockSpec((1 << p, 1), lambda ib, ih, ik, iq: (0, 0))
    out_kv_spec = pl.BlockSpec((1, 1, block_kv, d),
                               lambda ib, ih, ik, iq: (ib, ih, ik, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_kv=block_kv, n_q_blocks=n_q, p=p,
            iters=iters, variant=variant,
        ),
        grid=(b, h, n_kv, n_q),
        in_specs=[qT_spec, kvT_spec, kvT_spec, qT_spec, rowT_spec, rowT_spec,
                  rowT_spec, tabT_spec],
        out_specs=[out_kv_spec, out_kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32)] * 2,
        interpret=common.interpret_flag(interpret),
    )(q, k, v, do, m, l, delta, table)

    # GQA: fold the per-q-head gradients back onto the KV heads.
    dk = dk_h.reshape(b, kh, group, s, d).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(b, kh, group, s, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


def _resolve_bwd_cfg(shape, dtype, block_q_bwd, block_kv_bwd, interpret):
    """Backward tile shapes: explicit kwargs > tuning cache > registry
    defaults, clamped to divide the sequence (``fit_block``).

    Lazy import: tuning.registry imports this module (circular otherwise).
    """
    from repro.kernels.tuning import dispatch

    cfg = dispatch.resolve(
        "flash_attention_bwd", shape, dtype,
        {"block_q": block_q_bwd, "block_kv": block_kv_bwd,
         "interpret": interpret},
    )
    s = shape[2]
    return (common.fit_block(s, cfg["block_q"]),
            common.fit_block(s, cfg["block_kv"]), cfg["interpret"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10,
                                                    11, 12))
def _flash(q, k, v, causal, sm_scale, block_q, block_kv, p, iters, variant,
           interpret, block_q_bwd, block_kv_bwd):
    out, _, _ = _fwd_call(q, k, v, causal, sm_scale, block_q, block_kv, p,
                          iters, variant, interpret, save_residuals=False)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_kv, p, iters,
               variant, interpret, block_q_bwd, block_kv_bwd):
    out, m, l = _fwd_call(q, k, v, causal, sm_scale, block_q, block_kv, p,
                          iters, variant, interpret, save_residuals=True)
    return out, (q, k, v, out, m, l)


def _flash_bwd(causal, sm_scale, block_q, block_kv, p, iters, variant,
               interpret, block_q_bwd, block_kv_bwd, res, g):
    q, k, v, out, m, l = res
    bq, bkv, interp = _resolve_bwd_cfg(
        q.shape, q.dtype, block_q_bwd, block_kv_bwd, interpret,
    )
    dq, dk, dv = _bwd_call(
        q, k, v, g, out, m, l, causal=causal, sm_scale=sm_scale, block_q=bq,
        block_kv=bkv, p=p, iters=iters, variant=variant, interpret=interp,
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "block_q", "block_kv", "p", "iters", "variant",
        "interpret", "block_q_bwd", "block_kv_bwd",
    ),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    p: int = common.DEFAULT_P,
    iters: int = 2,
    variant: str = "feedback",
    interpret: bool | None = None,
    block_q_bwd: int | None = None,
    block_kv_bwd: int | None = None,
) -> jnp.ndarray:
    """q: (B, H, S, D); k/v: (B, KH, S, D) with H % KH == 0.  Returns (B,H,S,D).

    Differentiable (see module docstring).  ``block_q_bwd``/``block_kv_bwd``
    pin the backward kernels' tile shapes; ``None`` resolves them through
    the tuning dispatch (``flash_attention_bwd`` entry), falling back to
    the registry defaults.
    """
    b, h, s, d = q.shape
    kh = k.shape[1]
    assert h % kh == 0, (h, kh)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0, (s, block_q, block_kv)
    return _flash(q, k, v, causal, sm_scale, block_q, block_kv, p, iters,
                  variant, interpret, block_q_bwd, block_kv_bwd)


def flash_attention_bwd_bench(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    do: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    p: int = common.DEFAULT_P,
    iters: int = 2,
    variant: str = "feedback",
    interpret: bool | None = None,
):
    """Autotuner entry for the backward kernels (``flash_attention_bwd``).

    ``block_q``/``block_kv`` here are the BACKWARD tile shapes; the forward
    runs at its own defaults.  Times one full vjp (fwd + both backward
    kernels) — the backward pair dominates, and the forward term is
    constant across candidates so the argmin is unchanged.
    """
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=causal, p=p, iters=iters, variant=variant,
            interpret=interpret, block_q_bwd=block_q, block_kv_bwd=block_kv,
        ),
        q, k, v,
    )
    return vjp(do)
