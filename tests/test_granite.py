"""Granite's published arithmetic in the program, against the benchmark's
plain float32 reference (``bench/reference/dense_gqa.py``).

The Granite smoke configuration (tied head, all four scalars: embedding
x12, scores /128, residual x0.22, logits /16) on seeded weights is served
as the engine serves it: the paged pool, the batch-1 prefill step, then
the paged decode step that the engine's tick runs, teacher-forced so
the program and the reference see one sequence.  Also here: MiniCPM's
depth-scaled residual, now the same ``residual_multiplier``, keeps its
logits bit for bit.
"""

import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import api
from repro.serving import Engine, EngineConfig, Request
from repro.serving.engine import prefill_batch

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.reference import dense_gqa  # noqa: E402

F32 = dict(dtype="float32", param_dtype="float32")
SCALARS = {"embedding_multiplier": 1.0, "attention_multiplier": None,
           "residual_multiplier": 1.0, "logits_scaling": 1.0}

# Relative L2 error of the program's logits against the reference.  Both
# compute in float32 here; the program divides through the Goldschmidt
# datapath at float32's (7, 2) point (relative error ~2^-20 per division)
# and sums in another order, which reads ~2e-7 over the two layers.  The
# int8 decode (int8 weights and KV, fixed-point Goldschmidt) reads ~1e-2
# and dropping any one scalar 0.24 or more, so 1e-4 passes the first by
# ~500x and fails the others by ~100x.
REL_L2 = 1e-4

# sha256 of MiniCPM smoke's forward and prefill logits (key 0, tokens of
# RandomState(0)) on the commit before scale_depth became the residual
# multiplier
MINICPM_DIGEST = ("5f53a19c432a4d342f217cb1b05489560841c88e"
                  "c3d20cbe3f39d522012e3fdf")


@pytest.fixture(scope="module")
def granite():
    """Smoke config, weights, a prompt and the tokens fed after it, and
    the reference's logits at each served position."""
    cfg = configs.get_smoke("granite-3-8b", **F32)
    params = api.init(cfg, jax.random.key(0))
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab, (12,))
    fed = rng.randint(0, cfg.vocab, (6,))
    seq = np.concatenate([prompt, fed])
    seq = np.pad(seq, (0, dense_gqa.padded_len(len(seq)) - len(seq)))
    arch = dense_gqa.arithmetic({
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
        **{k: getattr(cfg, k) for k in SCALARS}})
    ref = np.asarray(dense_gqa.logits_at(
        params, jnp.asarray(seq, jnp.int32), jnp.int32(len(prompt) - 1),
        n_out=len(fed) + 1, arch=arch))
    return cfg, params, prompt, fed, ref


def program_logits(cfg, params, prompt, fed):
    """Last-position logits of the engine's prefill and of one paged
    decode step per token of ``fed``, through the engine's pool."""
    eng = Engine(cfg, params, EngineConfig(n_slots=2, pool="paged",
                                           page_size=4,
                                           page_reserve="worst"))
    pool = eng._make_pool()
    req = Request(rid=0, prompt=prompt, max_new_tokens=len(fed) + 1)
    slot = int(pool.alloc(req))
    logits, states, _ = eng._prefill(eng.params, prefill_batch(cfg, req))
    pool.write(slot, states, req=req, logits=logits)
    decode = jax.jit(lambda p, c, cur, tok, table: eng._decode(
        p, c, cur, {"token": tok}, page_table=table))
    out = [np.asarray(logits[0, -1], np.float32)]
    cur = np.zeros(2, np.int32)
    tok = np.zeros((2, 1), np.int32)
    for i, t in enumerate(fed):
        cur[slot], tok[slot, 0] = len(prompt) + i, t
        lg, pool.cache = decode(eng.params, pool.cache, jnp.asarray(cur),
                                jnp.asarray(tok), jnp.asarray(pool.table))
        out.append(np.asarray(lg[slot, -1], np.float32))
    return np.stack(out)


def rel_l2(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_program_matches_the_reference(granite, impl):
    cfg, params, prompt, fed, ref = granite
    got = program_logits(dataclasses.replace(cfg, kernel_impl=impl),
                         params, prompt, fed)
    assert rel_l2(got, ref) < REL_L2


@pytest.mark.parametrize("fault", [
    {"quant": "int8"}, *({k: v} for k, v in SCALARS.items())],
    ids=["int8_decode", *(f"without_{k}" for k in SCALARS)])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_fault_fails_the_tolerance(granite, impl, fault):
    """The int8 decode, or the program with one scalar at its default,
    lies outside the tolerance the sound program meets."""
    cfg, params, prompt, fed, ref = granite
    got = program_logits(dataclasses.replace(cfg, kernel_impl=impl,
                                             **fault), params, prompt, fed)
    assert rel_l2(got, ref) > REL_L2 * 10


@pytest.mark.parametrize("key", list(SCALARS))
def test_each_scalar_alone_moves_the_logits(granite, key):
    cfg, params, prompt, fed, _ = granite
    none = dataclasses.replace(cfg, **SCALARS)
    one = dataclasses.replace(none, **{key: getattr(cfg, key)})
    a = program_logits(none, params, prompt, fed)
    b = program_logits(one, params, prompt, fed)
    assert rel_l2(b, a) > 1e-2


def test_minicpm_depth_scaled_residual_is_bit_identical():
    cfg = configs.get_smoke("minicpm-2b")
    assert cfg.residual_multiplier == 1.4 / cfg.n_layers ** 0.5
    params = api.init(cfg, jax.random.key(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (2, 16)), jnp.int32)
    logits = jax.jit(lambda p, t: api.forward(cfg, p, {"tokens": t}))(
        params, tokens)
    last, _, _ = jax.jit(lambda p, t: api.prefill(cfg, p, {"tokens": t}))(
        params, tokens)
    h = hashlib.sha256()
    for a in (logits, last):
        h.update(np.asarray(a, np.float32).tobytes())
    assert h.hexdigest() == MINICPM_DIGEST
