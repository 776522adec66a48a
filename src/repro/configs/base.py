"""ArchConfig: the single config schema every assigned architecture fills in.

A config fully determines the parameter pytree, the layer stack pattern
(dense / MoE / SSM / hybrid / enc-dec / VLM), the numerics policy threading
the paper's Goldschmidt datapaths through the stack, and the shapes the
launcher lowers.  One ``<arch>.py`` per assigned architecture instantiates
this (plus a reduced ``smoke()`` variant per family for CPU tests).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.goldschmidt import target_bits_for
from repro.core.policy import NumericsPolicy


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # None -> d_model // n_heads

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1  # a layer i has MoE FFN iff n_experts>0 and i % moe_every == moe_every-1
    capacity_factor: float = 1.25
    moe_group_size: int = 512  # GShard group size (tokens)
    moe_chunk_groups: int = 16  # groups per scan step (memory bound, see DESIGN §8)

    # SSM (mamba1)
    ssm_state: int = 0
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None  # None -> ceil(d_model / 16)

    # hybrid (jamba): layer i is attention iff i % attn_every == attn_every-1
    attn_every: int = 0  # 0 -> all layers use the family default mixer

    # positional / norm
    rope_theta: float = 10000.0
    pos: str = "rope"  # rope | mrope | learned | none
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-5

    # enc-dec (whisper): n_layers applies to the decoder; encoder below
    n_enc_layers: int = 0
    enc_seq: int = 1500  # fixed encoder context (audio frames)
    frontend: str = "none"  # none | audio_stub | vision_stub

    # misc
    tie_embeddings: bool = False
    scale_depth: float = 0.0  # minicpm depth-scaled residual (0 = off)
    act: str = "silu"  # silu | gelu
    dtype: str = "bfloat16"  # activation dtype
    param_dtype: str = "float32"

    # numerics: the paper's technique, framework-wide.  gs_p_bits/gs_iters
    # left None derive the (ROM width, pass count) pair per division site
    # from the compute dtype via precision_policy: bf16 activations run
    # seed-only (p=8, 0 passes), fp32 the paper's (7, 2).
    policy_mode: str = "gs_feedback"  # exact | gs_pipelined | gs_feedback
    gs_p_bits: Optional[int] = None  # None -> derived (seed/iteration trade)
    gs_iters: Optional[int] = None  # None -> derived from dtype
    kernel_impl: str = "jnp"  # jnp | pallas (interpreted on CPU)
    quant: str = "none"  # none | int8: per-tensor int8 weights + int8 KV
    # arena + every GS division site through the fixed-point integer
    # datapath (core/fixed_point_jax) — the quantized serving route

    # structure / performance knobs
    remat: bool = True
    scan_layers: bool = True
    attn_q_block: int = 512
    attn_kv_block: int = 1024
    attn_block_skip: bool = False  # skip fully-masked causal blocks (opt)
    attn_seq_shard: bool = False  # shard q-block axis over 'model' (opt;
    # for archs whose head count doesn't divide the TP axis)
    seq_parallel: bool = False  # shard the residual stream's seq dim over
    # 'model' (full SP: projections/norms/logits local over s; KV
    # all-gathered per layer).  Pair with attn_seq_shard and
    # attn_q_block = seq_len / model_axis.
    zero3_pods: bool = False  # shard params/optimizer over the pod axis
    # too (ZeRO-3 across pods; multi-pod meshes only)
    mamba_chunk: int = 256
    max_seq: int = 4096  # fallback cache length when a shape doesn't say

    def __post_init__(self):
        period = self.period
        if self.n_layers % period:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} not divisible by "
                f"stack period {period}"
            )
        if self.n_heads % max(self.n_kv_heads, 1):
            raise ValueError(f"{self.name}: heads {self.n_heads} % kv {self.n_kv_heads}")

    # -- derived -------------------------------------------------------------

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank_(self) -> int:
        return self.dt_rank if self.dt_rank is not None else -(-self.d_model // 16)

    @property
    def period(self) -> int:
        """Length of the repeating layer pattern (scan superblock)."""
        p = 1
        if self.attn_every:
            p = self.attn_every
        if self.n_experts and self.moe_every > 1:
            p = _lcm(p, self.moe_every)
        return p

    @property
    def n_groups(self) -> int:
        return self.n_layers // self.period

    def mixer_kind(self, i: int) -> str:
        """Mixer of layer i: 'attn' or 'mamba'."""
        if self.family == "ssm":
            return "mamba"
        if self.family == "hybrid":
            return "attn" if (i % self.attn_every) == self.attn_every - 1 else "mamba"
        return "attn"

    def ffn_kind(self, i: int) -> str:
        """FFN of layer i: 'mlp', 'moe' or 'none'."""
        if self.family == "ssm":
            return "none"  # mamba1 blocks carry no separate FFN
        if self.n_experts and (i % self.moe_every) == self.moe_every - 1:
            return "moe"
        return "mlp"

    def block_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, ffn) for each position of one superblock."""
        return tuple(
            (self.mixer_kind(i), self.ffn_kind(i)) for i in range(self.period)
        )

    def policy(self) -> NumericsPolicy:
        """Model-stack policy: accuracy budget = the COMPUTE dtype.

        Norms/softmax run their statistics in fp32, but the results land
        in ``dtype``-wide activations — so the Goldschmidt sites budget
        ``target_bits`` for that dtype, not for the fp32 intermediates
        (bf16 models stop paying fp32-grade iteration counts).
        """
        fmt = None
        if self.quant != "none":
            if self.quant != "int8":
                raise ValueError(f"unknown quant mode {self.quant!r}")
            from repro.core.formats import format_for

            fmt = format_for("int8")
        return NumericsPolicy(
            mode=self.policy_mode, p_bits=self.gs_p_bits, iters=self.gs_iters,
            target_bits=target_bits_for(self.dtype), fmt=fmt,
        )

    def optimizer_policy(self) -> NumericsPolicy:
        """Optimizer policy: accuracy budget = the PARAM/state dtype.

        AdamW's divide/sqrt feed fp32 optimizer state and fp32 master
        params; its compute dtype is ``param_dtype``, so fp32 training
        keeps the bit-identical (7, 2) datapath while low-precision
        parameter experiments shed passes automatically.
        """
        return NumericsPolicy(
            mode=self.policy_mode, p_bits=self.gs_p_bits, iters=self.gs_iters,
            target_bits=target_bits_for(self.param_dtype),
        )


def _lcm(a: int, b: int) -> int:
    import math

    return a * b // math.gcd(a, b)


# -- the four LM shapes every arch is paired with ---------------------------

SHAPES = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}


def shape_applicable(cfg: ArchConfig, shape_name: str) -> Tuple[bool, str]:
    """Whether a (arch, shape) cell runs, and why not if it doesn't.

    Per the assignment: long_500k needs sub-quadratic attention — run for
    SSM/hybrid, skip for pure full-attention archs (incl. enc-dec & VLM).
    """
    if shape_name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, (
            "long_500k requires sub-quadratic attention; "
            f"{cfg.name} is a pure full-attention arch (family={cfg.family})"
        )
    return True, ""
