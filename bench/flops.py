"""Operations and bytes the algorithm needs, from shapes alone.

Counts are of multiply-adds times two (FLOPs) for the work the model
requires, not what a kernel happens to compute: causal attention counts
the lower triangle of the score matrix (``s (s + 1) / 2`` entries), and
a decode step attends over the positions that exist, not over the
padded cache.  ``arch`` is a configuration's size dict (``n_layers``,
``d_model``, ``n_heads``, ``n_kv_heads``, ``d_ff``, ``vocab``,
optionally ``head_dim``).
"""

from __future__ import annotations


def _dims(arch: dict):
    d, H = arch["d_model"], arch["n_heads"]
    hd = arch.get("head_dim") or d // H
    return arch["n_layers"], d, H, arch["n_kv_heads"], hd, arch["d_ff"], \
        arch["vocab"]


def matmul_macs_per_token(arch: dict) -> int:
    """Weight multiply-adds of one token: projections, gated MLP, head."""
    L, d, H, KH, hd, F, V = _dims(arch)
    per_layer = d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * F
    return L * per_layer + d * V


def causal_pairs(s: int) -> int:
    """(query, key) pairs of causal self-attention over ``s`` tokens."""
    return s * (s + 1) // 2


def flash_prefill(arch: dict, s: int, act_bytes: int = 2):
    """(flops, bytes) of the flash-attention kernel over all layers for
    one prompt of ``s`` tokens: q k^T and p v over the causal half;
    q, k, v read once and the output written once."""
    L, _, H, KH, hd, _, _ = _dims(arch)
    flops = L * 4 * H * hd * causal_pairs(s)
    nbytes = L * s * hd * (2 * H + 2 * KH) * act_bytes
    return flops, nbytes


def prefill_flops(arch: dict, s: int) -> int:
    """Model FLOPs of prefilling ``s`` prompt tokens."""
    return 2 * matmul_macs_per_token(arch) * s + flash_prefill(arch, s)[0]


def decode_flops(arch: dict, context: int) -> int:
    """Model FLOPs of one decode token that attends over ``context``
    positions (itself included)."""
    L, _, H, _, hd, _, _ = _dims(arch)
    return 2 * matmul_macs_per_token(arch) + L * 4 * H * hd * context


def request_decode_flops(arch: dict, prompt_len: int, n_tokens: int) -> int:
    """Decode FLOPs of a request that produced ``n_tokens`` tokens: the
    first comes from the prefill; token ``i >= 1`` is computed at
    position ``prompt_len + i - 1`` and attends over ``prompt_len + i``
    positions."""
    L, _, H, _, hd, _, _ = _dims(arch)
    n = max(n_tokens - 1, 0)
    ctx_sum = n * prompt_len + n * (n + 1) // 2
    return 2 * matmul_macs_per_token(arch) * n + L * 4 * H * hd * ctx_sum


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float):
    """(share of the roofline in %, which bound binds) for work done in
    ``seconds``: the least time the chip could take over the time taken."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bw
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
