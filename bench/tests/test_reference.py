"""The plain float32 reference against the program, at smoke widths."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, weights
from bench.reference import dense_gqa

SEEDS = (1, 2, 3)
# Granite 3.0's published scalars (ibm-granite/granite-3.0-8b-instruct)
GRANITE = {"embedding_multiplier": 12.0, "attention_multiplier": 0.0078125,
           "residual_multiplier": 0.22, "logits_scaling": 16.0}
BASE = {"rms_norm_eps": 1e-05, "rope_theta": 10000.0}
ARCH = {"n_layers": 2, "d_model": 128, "n_heads": 8, "n_kv_heads": 4,
        "head_dim": 16, "d_ff": 192, "vocab": 512}


def _tokens(seed, vocab, n=dense_gqa.Q_BLOCK):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, n),
                       jnp.int32)


def _weights(tied, seed=3, std=0.1):
    # std 0.1, not the published 0.02: larger weights give every scalar
    # a visible effect on the logits at smoke widths
    return weights.make(dict(ARCH, tie_word_embeddings=tied),
                        jax.random.key(seed), std=std)


@pytest.mark.parametrize("name", ["tiny.chat", "tiny-tied.chat"])
def test_reference_is_the_programs_model_in_exact_arithmetic(tiny_cell,
                                                             name):
    """With the program's exact float32 path (no Goldschmidt, no
    kernels), its logits and the reference's agree to float32 rounding:
    the reference reads the same weights the same way (rotary form,
    query-to-KV head map, norms, gated MLP, untied or tied head)."""
    from repro.models import api

    cell = tiny_cell(name)
    cfg = dataclasses.replace(harness.build_cfg(cell), kernel_impl="jnp",
                              policy_mode="exact", dtype="float32")
    params = weights.make(harness.arch_sizes(cfg, cell.config),
                          jax.random.key(5), std=0.02)
    harness._check_layout(cfg, params)
    S = dense_gqa.Q_BLOCK
    toks = np.random.default_rng(0).integers(0, cfg.vocab, S)
    with jax.default_matmul_precision("highest"):
        want = api.forward(cfg, params, {"tokens": jnp.asarray(toks[None])})[0]
    got = dense_gqa.logits_at(params, jnp.asarray(toks, jnp.int32),
                              jnp.int32(0), n_out=S,
                              arch=dense_gqa.arithmetic(cell.config))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_logits_at_ignores_what_follows(tiny_cell):
    cell = tiny_cell()
    cfg = harness.build_cfg(cell)
    params = weights.make(harness.arch_sizes(cfg, cell.config),
                          jax.random.key(1), std=0.02)
    S = dense_gqa.Q_BLOCK
    a = np.random.default_rng(1).integers(0, cfg.vocab, S)
    b = a.copy()
    b[100:] = 7
    kw = dict(n_out=40, arch=dense_gqa.arithmetic(cell.config))
    la = dense_gqa.logits_at(params, jnp.asarray(a, jnp.int32),
                             jnp.int32(60), **kw)
    lb = dense_gqa.logits_at(params, jnp.asarray(b, jnp.int32),
                             jnp.int32(60), **kw)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -- the reference as it stood before the configuration stated its
# arithmetic, frozen whole: the eps and theta the program's ArchConfig
# gave it, 1/sqrt(hd) scores, no scalars, the untied head

def _f_ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _f_rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _f_rope(x, pos, theta):
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos[:, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _f_attention(q, k, v):
    S, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    cols = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * 256, 256, 0)
        s = _f_ein("qhd,khd->hqk", qb, k) * scale
        rows = i * 256 + jnp.arange(256)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _f_ein("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(S // 256))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("n_out", "eps", "theta"))
def _frozen_logits_at(params, tokens, start, *, n_out, eps, theta):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = f32(params["embed"])[tokens]

    def layer(x, p):
        h = _f_rmsnorm(x, f32(p["norm1"]["scale"]), eps)
        a = p["attn"]
        q = _f_rope(_f_ein("sd,dhk->shk", h, f32(a["wq"])), pos, theta)
        k = _f_rope(_f_ein("sd,dhk->shk", h, f32(a["wk"])), pos, theta)
        v = _f_ein("sd,dhk->shk", h, f32(a["wv"]))
        x = x + _f_ein("shk,hkd->sd", _f_attention(q, k, v), f32(a["wo"]))
        h = _f_rmsnorm(x, f32(p["norm2"]["scale"]), eps)
        m = p["mlp"]
        u = (jax.nn.silu(_f_ein("sd,df->sf", h, f32(m["w_gate"])))
             * _f_ein("sd,df->sf", h, f32(m["w_in"])))
        return x + _f_ein("sf,fd->sd", u, f32(m["w_out"])), None

    x, _ = jax.lax.scan(layer, x, params["layers"]["pos0"])
    x = jax.lax.dynamic_slice_in_dim(x, start, n_out, 0)
    h = _f_rmsnorm(x, f32(params["final_norm"]["scale"]), eps)
    return _f_ein("sd,dv->sv", h, f32(params["lm_head"]))


@pytest.mark.parametrize("config", [
    {"rms_norm_eps": 1e-05, "rope_theta": 1000000},
    {"rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
     "tie_word_embeddings": False}], ids=["no-optional-key", "untied"])
def test_without_optional_keys_the_logits_are_the_frozen_ones(config):
    """A file that states none of Granite's keys gets, bit for bit, the
    logits of the reference before the file stated its arithmetic."""
    params = _weights(tied=False, std=0.02)
    toks = _tokens(4, ARCH["vocab"], 2 * dense_gqa.Q_BLOCK)
    got = dense_gqa.logits_at(params, toks, jnp.int32(100), n_out=64,
                              arch=dense_gqa.arithmetic(config))
    want = _frozen_logits_at(params, toks, jnp.int32(100), n_out=64,
                             eps=1e-5, theta=1000000.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _numpy_forward(params, tokens, cfg):
    """Granite's forward pass in float64 NumPy, one token at a time
    against a growing KV cache: no blocks, no scan, no masks."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    lay = p["layers"]["pos0"]
    L, d = ARCH["n_layers"], ARCH["d_model"]
    H, KH, hd = ARCH["n_heads"], ARCH["n_kv_heads"], ARCH["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    inv = theta ** (-np.arange(hd // 2) / (hd // 2))

    def norm(x, g):
        return x / np.sqrt(np.mean(x * x) + eps) * g

    def rope(x, t):  # x (heads, hd)
        c, s = np.cos(t * inv), np.sin(t * inv)
        x1, x2 = x[:, :hd // 2], x[:, hd // 2:]
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    keys = [[] for _ in range(L)]
    vals = [[] for _ in range(L)]
    out = []
    for t, tok in enumerate(np.asarray(tokens)):
        x = p["embed"][tok] * cfg["embedding_multiplier"]
        for i in range(L):
            h = norm(x, lay["norm1"]["scale"][i])
            a = lay["attn"]
            q = rope(np.einsum("d,dhk->hk", h, a["wq"][i]), t)
            keys[i].append(rope(np.einsum("d,dhk->hk", h, a["wk"][i]), t))
            vals[i].append(np.einsum("d,dhk->hk", h, a["wv"][i]))
            K, V = np.stack(keys[i]), np.stack(vals[i])  # (t+1, KH, hd)
            o = np.empty((H, hd))
            for j in range(H):
                s = K[:, j // (H // KH)] @ q[j] * cfg["attention_multiplier"]
                w = np.exp(s - s.max())
                o[j] = (w / w.sum()) @ V[:, j // (H // KH)]
            x = x + cfg["residual_multiplier"] * np.einsum(
                "hk,hkd->d", o, a["wo"][i])
            h = norm(x, lay["norm2"]["scale"][i])
            m = lay["mlp"]
            g = h @ m["w_gate"][i]
            u = g / (1 + np.exp(-g)) * (h @ m["w_in"][i])
            x = x + cfg["residual_multiplier"] * (u @ m["w_out"][i])
        h = norm(x, p["final_norm"]["scale"])
        out.append(h @ p["embed"].T / cfg["logits_scaling"])
    assert d == p["embed"].shape[1]
    return np.stack(out)


def test_granite_form_agrees_with_a_float64_forward_pass():
    """All four scalars away from 1 and a tied head: the reference gives
    the logits of an independent float64 forward pass.  The tolerance is
    float32 rounding: each of the reference's float32 sums (over 128 to
    192 terms, two layers deep, then 128 for the head) is off by a few
    units in the last place of its terms, which reads 4e-7 of the
    largest logit here; 1e-5 of it leaves room, while a scalar read
    wrongly moves the logits by 1e-2 of it or more (the next test)."""
    config = dict(BASE, tie_word_embeddings=True, **GRANITE)
    params = _weights(tied=True)
    assert "lm_head" not in params
    toks = _tokens(6, ARCH["vocab"])
    got = np.asarray(dense_gqa.logits_at(
        params, toks, jnp.int32(0), n_out=dense_gqa.Q_BLOCK,
        arch=dense_gqa.arithmetic(config)), np.float64)
    want = _numpy_forward(params, toks, config)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)


@pytest.mark.parametrize("key", sorted(GRANITE) + ["tie_word_embeddings"])
def test_each_key_alone_moves_the_logits(key):
    """No key can be read and then left out: stating one alone changes
    the logits by far more than rounding."""
    params = _weights(tied=False)
    toks = _tokens(8, ARCH["vocab"])
    value = GRANITE.get(key, True)

    def logits(config):
        return np.asarray(dense_gqa.logits_at(
            params, toks, jnp.int32(0), n_out=dense_gqa.Q_BLOCK,
            arch=dense_gqa.arithmetic(config)))

    base = logits(BASE)
    moved = logits(dict(BASE, **{key: value}))
    assert np.abs(moved - base).max() > 1e-2 * np.abs(base).max()


def test_arithmetic_needs_eps_and_theta():
    with pytest.raises(KeyError, match="rope_theta"):
        dense_gqa.arithmetic({"rms_norm_eps": 1e-5})


@pytest.mark.parametrize("seed", SEEDS)
def test_served_tokens_agree_with_the_reference(tiny_cell, seed):
    """Engine.run (Pallas prefill, paged decode, bf16) as a benchmark run
    drives it: every compared gap is within the cell's limit."""
    cell = tiny_cell()
    r = control.reading(cell, seed, 1.0, require_tpu=False)
    assert r["requests"] == cell.mix["check"]["requests"]
    assert 0.0 <= r["max_logit_gap"] <= cell.limits["max_logit_gap"]


@pytest.mark.parametrize("seed", SEEDS)
def test_lower_precision_fails_the_limit(tiny_cell, seed):
    """The control, the program's int8 path (one precision below the
    bfloat16 the configuration computes in), fails the same limit."""
    cell = tiny_cell()
    r = control.reading(cell, seed, 1.0, control.CONTROL, require_tpu=False)
    assert r["max_logit_gap"] > cell.limits["max_logit_gap"]
