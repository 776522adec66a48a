"""Seeded random weights of a dense GQA decoder, made by the benchmark.

The benchmark makes the weights itself, so the reference never reads
anything the program made.  The pytree follows the layout the program's
engine takes (``repro.models`` dense stack, scanned over layers):

    embed (V, d); lm_head (d, V), absent where the head is tied to
    embed (``tie_word_embeddings``); final_norm.scale (d,)
    layers.pos0: norm1.scale, norm2.scale (L, d)
                 attn.wq (L, d, H, hd), attn.wk / attn.wv (L, d, KH, hd),
                 attn.wo (L, H, hd, d)
                 mlp.w_gate / mlp.w_in (L, d, F), mlp.w_out (L, F, d)

Matrices are normal with the published ``initializer_range`` (0.02 for
both configurations here) and norm scales are 1, as the published
models initialize them.  All of it is made on the device in one jitted
call, in the parameter dtype the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(arch: dict) -> dict:
    """Leaf shapes of the pytree, from the configuration's sizes and its
    ``tie_word_embeddings``."""
    L, d, V = arch["n_layers"], arch["d_model"], arch["vocab"]
    H, KH, F = arch["n_heads"], arch["n_kv_heads"], arch["d_ff"]
    hd = arch.get("head_dim") or d // H
    head = {} if arch.get("tie_word_embeddings") else {"lm_head": (d, V)}
    return {
        "embed": (V, d),
        **head,
        "final_norm": {"scale": (d,)},
        "layers": {"pos0": {
            "norm1": {"scale": (L, d)},
            "norm2": {"scale": (L, d)},
            "attn": {"wq": (L, d, H, hd), "wk": (L, d, KH, hd),
                     "wv": (L, d, KH, hd), "wo": (L, H, hd, d)},
            "mlp": {"w_gate": (L, d, F), "w_in": (L, d, F),
                    "w_out": (L, F, d)},
        }},
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make(arch: dict, key, *, std: float, dtype=jnp.float32,
         out_shardings=None):
    """The weights from ``key``, on the device(s), in one call."""
    tree = shapes(arch)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]]

    def build(k):
        ks = jax.random.split(k, len(leaves))
        out = []
        for kk, shape, path in zip(ks, leaves, paths):
            if "scale" in path:
                out.append(jnp.ones(shape, dtype))
            else:
                out.append((std * jax.random.normal(kk, shape, jnp.float32)
                            ).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build, out_shardings=out_shardings)(key)


def abstract(arch: dict, dtype=jnp.float32):
    """ShapeDtypeStructs of the pytree (no device work)."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dtype),
                        shapes(arch), is_leaf=_is_shape)
