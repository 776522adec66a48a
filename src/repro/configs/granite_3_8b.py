"""granite-3-8b — IBM Granite 3.0 8B Instruct, GQA with Granite's scalars
[hf: ibm-granite/granite-3.0-8b-instruct, GraniteForCausalLM].

40L d_model=4096 32H (GQA kv=8) d_ff=12800 vocab=49155, head_dim=128,
rope_theta 10000, 4096 positions, tied embeddings.  The published
scalars: x = 12 * embed[t]; scores q.k / 128 (attention_multiplier
1/128, not 1/sqrt(128)); x += 0.22 * block(x); logits = head(x) / 16.
"""

from repro.configs.base import ArchConfig

GRANITE_SCALARS = dict(
    embedding_multiplier=12.0, attention_multiplier=0.0078125,
    residual_multiplier=0.22, logits_scaling=16.0,
)


def config(**over) -> ArchConfig:
    kw = dict(
        name="granite-3-8b", family="dense", n_layers=40, d_model=4096,
        n_heads=32, n_kv_heads=8, d_ff=12800, vocab=49155,
        rope_theta=10000.0, max_seq=4096, tie_embeddings=True,
        **GRANITE_SCALARS,
    )
    kw.update(over)
    return ArchConfig(**kw)


def smoke(**over) -> ArchConfig:
    # 4 KV heads: a 4-wide 'model' axis shards them, as it shards the
    # published 8; vocab 257 does not divide it, as 49155 does not
    kw = dict(
        name="granite-smoke", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=4, d_ff=160, vocab=257, max_seq=64,
        tie_embeddings=True, **GRANITE_SCALARS,
    )
    kw.update(over)
    return ArchConfig(**kw)
