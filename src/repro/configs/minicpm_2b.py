"""minicpm-2b — WSD schedule, llama-like arch [arXiv:2404.06395; hf].

40L d_model=2304 36H (MHA kv=36) d_ff=5760 vocab=122753, head_dim=64.
Depth-scaled residuals (scale_depth=1.4: each block's output is scaled by
scale_depth / sqrt(n_layers), the ``residual_multiplier``) and tied
embeddings; trained with the WSD (warmup-stable-decay) schedule —
provided by repro.optim.schedules.
"""

from repro.configs.base import ArchConfig

SCALE_DEPTH = 1.4


def _build(kw: dict, over: dict) -> ArchConfig:
    kw.update(over)
    kw.setdefault("residual_multiplier",
                  SCALE_DEPTH / (kw["n_layers"] ** 0.5))
    return ArchConfig(**kw)


def config(**over) -> ArchConfig:
    return _build(dict(
        name="minicpm-2b", family="dense", n_layers=40, d_model=2304,
        n_heads=36, n_kv_heads=36, d_ff=5760, vocab=122753,
        tie_embeddings=True,
    ), over)


def smoke(**over) -> ArchConfig:
    return _build(dict(
        name="minicpm-smoke", family="dense", n_layers=2, d_model=72,
        n_heads=6, n_kv_heads=6, d_ff=144, vocab=256,
        tie_embeddings=True, max_seq=64,
    ), over)
