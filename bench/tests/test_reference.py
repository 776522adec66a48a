"""The plain float32 reference against the program, at smoke widths."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, weights
from bench.reference import dense_gqa

SEEDS = (1, 2, 3)


def test_reference_is_the_programs_model_in_exact_arithmetic(tiny_cell):
    """With the program's exact float32 path (no Goldschmidt, no
    kernels), its logits and the reference's agree to float32 rounding:
    the reference reads the same weights the same way (rotary form,
    query-to-KV head map, norms, gated MLP)."""
    from repro.models import api

    cell = tiny_cell()
    cfg = dataclasses.replace(harness.build_cfg(cell), kernel_impl="jnp",
                              policy_mode="exact", dtype="float32")
    params = weights.make(harness.arch_sizes(cfg), jax.random.key(5),
                          std=0.02)
    harness._check_layout(cfg, params)
    S = dense_gqa.Q_BLOCK
    toks = np.random.default_rng(0).integers(0, cfg.vocab, S)
    with jax.default_matmul_precision("highest"):
        want = api.forward(cfg, params, {"tokens": jnp.asarray(toks[None])})[0]
    got = dense_gqa.logits_at(params, jnp.asarray(toks, jnp.int32),
                              jnp.int32(0), n_out=S, eps=cfg.norm_eps,
                              theta=cfg.rope_theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_logits_at_ignores_what_follows(tiny_cell):
    cfg = harness.build_cfg(tiny_cell())
    params = weights.make(harness.arch_sizes(cfg), jax.random.key(1),
                          std=0.02)
    S = dense_gqa.Q_BLOCK
    a = np.random.default_rng(1).integers(0, cfg.vocab, S)
    b = a.copy()
    b[100:] = 7
    kw = dict(n_out=40, eps=cfg.norm_eps, theta=cfg.rope_theta)
    la = dense_gqa.logits_at(params, jnp.asarray(a, jnp.int32),
                             jnp.int32(60), **kw)
    lb = dense_gqa.logits_at(params, jnp.asarray(b, jnp.int32),
                             jnp.int32(60), **kw)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


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
