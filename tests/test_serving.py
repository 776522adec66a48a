"""Continuous-batching engine: parity, slot recycling, sampler, pool.

The load-bearing property is batched-vs-sequential parity: N staggered
variable-length requests served through shared slots must match N
independent single-request runs token-for-token (greedy, fp32).  That
exercises the per-slot cur_index vector through attention masks, rope
positions, cache writes and the slot pool in one shot.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import EXACT, GS_FEEDBACK
from repro.models import api
from repro.serving import (Engine, EngineConfig, FINISH_LENGTH, FINISH_STOP,
                           PagedCachePool, Request, SamplingParams,
                           SlotCachePool, generate_sequential, sample_tokens)

F32 = dict(dtype="float32", param_dtype="float32")


def _requests(cfg, rng, specs):
    """specs: list of (prompt_len, max_new_tokens, arrival_time)."""
    return [
        Request(rid=i, prompt=rng.randint(0, cfg.vocab, (s,)),
                max_new_tokens=g, arrival_time=t,
                frames=(rng.randn(cfg.enc_seq, cfg.d_model)
                        .astype(np.float32) * 0.1
                        if cfg.family == "encdec" else None))
        for i, (s, g, t) in enumerate(specs)]


def _assert_parity(cfg, params, reqs, outs):
    for r in reqs:
        ref = generate_sequential(cfg, params, r)
        got = outs[r.rid].tokens
        np.testing.assert_array_equal(
            ref, got, err_msg=f"req {r.rid} (prompt {r.prompt_len}, "
                              f"gen {r.max_new_tokens})")


class TestEngineParity:
    def test_staggered_variable_length_parity(self):
        """3+ staggered requests, distinct prompt/gen lengths, 2 slots:
        queueing + mid-flight admission + slot churn, token-for-token."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(0))
        rng = np.random.RandomState(0)
        reqs = _requests(cfg, rng, [(6, 5, 0.0), (9, 8, 0.0),
                                    (4, 3, 0.02), (7, 6, 0.03)])
        eng = Engine(cfg, params, EngineConfig(n_slots=2))
        outs, metrics = eng.run(reqs)
        _assert_parity(cfg, params, reqs, outs)
        assert metrics.decode_ticks > 0
        assert metrics.decode_tokens == sum(
            r.max_new_tokens - 1 for r in reqs)
        assert metrics.prefill_tokens == sum(r.prompt_len for r in reqs)
        assert metrics.first_tokens == len(reqs)
        assert set(metrics.ttft_s) == {r.rid for r in reqs}
        assert all(t >= 0 for t in metrics.ttft_s.values())

    def test_single_slot_recycling_no_stale_leak(self):
        """n_slots=1 forces every request through the SAME slot: any
        stale KV/SSM state leaking across free/alloc breaks parity."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(1))
        rng = np.random.RandomState(1)
        reqs = _requests(cfg, rng, [(8, 4, 0.0), (5, 6, 0.0), (10, 3, 0.0)])
        eng = Engine(cfg, params, EngineConfig(n_slots=1))
        outs, metrics = eng.run(reqs)
        _assert_parity(cfg, params, reqs, outs)
        assert metrics.occupancy == 1.0  # one slot, always busy

    def test_ssm_state_recycling(self):
        """Mamba SSM state is unmasked — recycling MUST zero it."""
        cfg = configs.get_smoke("falcon-mamba-7b", **F32)
        params = api.init(cfg, jax.random.key(2))
        rng = np.random.RandomState(2)
        reqs = _requests(cfg, rng, [(7, 5, 0.0), (4, 4, 0.0), (9, 6, 0.0)])
        eng = Engine(cfg, params, EngineConfig(n_slots=1))
        outs, _ = eng.run(reqs)
        _assert_parity(cfg, params, reqs, outs)

    def test_static_scheduler_matches_continuous_outputs(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(3))
        rng = np.random.RandomState(3)
        reqs = _requests(cfg, rng, [(6, 4, 0.0), (8, 7, 0.0), (5, 5, 0.0)])
        eng = Engine(cfg, params, EngineConfig(n_slots=2))
        outs_c, _ = eng.run(reqs, scheduler="continuous")
        outs_s, m_s = eng.run(reqs, scheduler="static")
        for r in reqs:
            np.testing.assert_array_equal(outs_c[r.rid].tokens,
                                          outs_s[r.rid].tokens)
        assert m_s.decode_ticks > 0

    def test_gen_1_no_decode_steps(self):
        """max_new_tokens=1: first token from prefill, zero decode ticks,
        tok/s reporting must not divide by zero."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(4))
        rng = np.random.RandomState(4)
        reqs = _requests(cfg, rng, [(6, 1, 0.0), (4, 1, 0.0)])
        eng = Engine(cfg, params, EngineConfig(n_slots=2))
        outs, metrics = eng.run(reqs)
        assert metrics.decode_ticks == 0
        assert metrics.decode_tok_per_s == 0.0
        assert metrics.occupancy == 0.0
        assert metrics.first_tokens == 2
        for r in reqs:
            assert outs[r.rid].tokens.shape == (1,)
            np.testing.assert_array_equal(
                generate_sequential(cfg, params, r), outs[r.rid].tokens)

    @pytest.mark.slow
    @pytest.mark.parametrize("arch,over", [
        ("jamba-1.5-large-398b", {"capacity_factor": 8.0}),
        ("qwen2-vl-72b", {}),
        ("whisper-large-v3", {}),
    ])
    def test_families_parity(self, arch, over):
        """Hybrid (SSM+MoE), mrope VLM and encdec (learned positions,
        cross-attention cache) through the per-slot decode path."""
        cfg = configs.get_smoke(arch, **F32, **over)
        params = api.init(cfg, jax.random.key(5))
        rng = np.random.RandomState(5)
        reqs = _requests(cfg, rng, [(4, 3, 0.0), (7, 5, 0.0), (10, 4, 0.0)])
        eng = Engine(cfg, params, EngineConfig(n_slots=2))
        outs, _ = eng.run(reqs)
        _assert_parity(cfg, params, reqs, outs)


class TestSchedulerDeterminism:
    """Stochastic streams are keyed on (request id, position), so the
    scheduler choice, the pool width and tick composition must not change
    a single sampled token (see engine.py "Scheduler-invariant
    sampling")."""

    def _cfg_params_reqs(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(11))
        rng = np.random.RandomState(11)
        reqs = [
            Request(rid=i, prompt=rng.randint(0, cfg.vocab, (s,)),
                    max_new_tokens=g, temperature=t, arrival_time=a)
            for i, (s, g, t, a) in enumerate([
                (6, 5, 0.9, 0.0), (9, 7, 0.0, 0.0),   # mixed greedy/sampled
                (4, 6, 1.3, 0.01), (7, 4, 0.7, 0.02),
                (5, 5, 0.9, 0.03)])]
        return cfg, params, reqs

    def test_identical_streams_across_schedulers_and_pool_widths(self):
        cfg, params, reqs = self._cfg_params_reqs()
        runs = {}
        for n_slots in (1, 2, 4):
            for scheduler in ("continuous", "static"):
                eng = Engine(cfg, params,
                             EngineConfig(n_slots=n_slots, top_k=8, seed=3))
                outs, _ = eng.run(reqs, scheduler=scheduler)
                runs[(n_slots, scheduler)] = {
                    r.rid: outs[r.rid].tokens for r in reqs}
        base = runs[(1, "continuous")]
        for key, toks in runs.items():
            for rid in base:
                np.testing.assert_array_equal(
                    base[rid], toks[rid],
                    err_msg=f"stream diverged for rid={rid} at {key}")

    def test_stochastic_stream_matches_sequential_reference(self):
        """The engine's in-tick key fold must equal the host-side fold the
        batch-1 sequential reference uses — the differential that pins
        the (rid, position) keying itself."""
        cfg, params, reqs = self._cfg_params_reqs()
        eng = Engine(cfg, params, EngineConfig(n_slots=2, top_k=8, seed=3))
        outs, _ = eng.run(reqs)
        for r in reqs:
            ref = generate_sequential(cfg, params, r, top_k=8, seed=3)
            np.testing.assert_array_equal(
                ref, outs[r.rid].tokens,
                err_msg=f"rid={r.rid} temp={r.temperature}")

    def test_different_seed_changes_sampled_rows_only(self):
        cfg, params, reqs = self._cfg_params_reqs()
        outs_a, _ = Engine(cfg, params, EngineConfig(
            n_slots=2, top_k=8, seed=3)).run(reqs)
        outs_b, _ = Engine(cfg, params, EngineConfig(
            n_slots=2, top_k=8, seed=4)).run(reqs)
        greedy = [r.rid for r in reqs if r.temperature == 0.0]
        sampled = [r.rid for r in reqs if r.temperature > 0.0]
        for rid in greedy:
            np.testing.assert_array_equal(outs_a[rid].tokens,
                                          outs_b[rid].tokens)
        assert any(not np.array_equal(outs_a[rid].tokens, outs_b[rid].tokens)
                   for rid in sampled)


class TestAdmissionLoop:
    def test_1k_request_trace_stays_bounded(self):
        """A 1k-request trace through a 4-slot pool: the deque-backed
        admission loop must drain it without quadratic rescans (every
        request identical -> one prefill compile, gen=1 -> no decode)."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(12))
        rng = np.random.RandomState(12)
        prompt = rng.randint(0, cfg.vocab, (4,))
        reqs = [Request(rid=i, prompt=prompt, max_new_tokens=1)
                for i in range(1000)]
        eng = Engine(cfg, params, EngineConfig(n_slots=4))
        outs, metrics = eng.run(reqs)
        assert metrics.n_requests == 1000
        assert metrics.first_tokens == 1000
        assert metrics.decode_ticks == 0
        assert len(outs) == 1000
        ref = outs[0].tokens
        for rid in (1, 499, 999):  # identical prompts -> identical tokens
            np.testing.assert_array_equal(ref, outs[rid].tokens)

    def test_1k_churn_with_backoff_requeues_serves_all(self):
        """1k requests against a bounded queue + paged pool: overflow
        requeues re-enter the pending deque in sorted order (bisect
        insertion) and freed slots recycle through the free-slot deque.
        Every request must finish exactly once with reason "length"."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(13))
        rng = np.random.RandomState(13)
        prompt = rng.randint(0, cfg.vocab, (4,))
        reqs = [Request(rid=i, prompt=prompt, max_new_tokens=1)
                for i in range(1000)]
        eng = Engine(cfg, params, EngineConfig(
            n_slots=4, s_max=8, pool="paged", page_size=4, prefix="off",
            max_queue=900, max_retries=5000, retry_backoff_s=0.0))
        outs, metrics = eng.run(reqs)
        assert len(outs) == 1000 and metrics.n_requests == 1000
        assert all(outs[i].finish_reason == FINISH_LENGTH
                   for i in range(1000))
        assert metrics.retried > 0    # overflow requeues really happened
        assert metrics.pool["free_slots"] == 4
        ref = outs[0].tokens
        for rid in (1, 499, 999):
            np.testing.assert_array_equal(ref, outs[rid].tokens)


class TestSlotCachePool:
    def _pool(self, n_slots=3):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        return cfg, SlotCachePool(cfg, n_slots, 32, jnp.float32)

    def test_alloc_free_cycle(self):
        _, pool = self._pool(2)
        a, b = pool.alloc(), pool.alloc()
        assert {a, b} == {0, 1} and pool.free_slots == 0
        with pytest.raises(RuntimeError):
            pool.alloc()
        pool.free(a)
        assert pool.free_slots == 1 and pool.alloc() == a
        with pytest.raises(ValueError):
            pool.free(5)

    def test_reset_zeroes_the_row_only(self):
        cfg, pool = self._pool(2)
        ones = jax.tree.map(lambda a: jnp.ones_like(a), pool.cache)
        pool.cache = ones
        pool.reset(0)
        for leaf in jax.tree.leaves(pool.row(0)):
            assert bool(jnp.all(leaf == 0))
        for leaf in jax.tree.leaves(pool.row(1)):
            assert bool(jnp.all(leaf == 1))

    def test_write_grafts_prefill_row(self):
        cfg, pool = self._pool(2)
        b = {"tokens": jnp.zeros((1, 5), jnp.int32)}
        params = api.init(cfg, jax.random.key(6))
        _, states, _ = api.prefill(cfg, params, b)
        pool.write(1, states)
        row = pool.row(1)
        # prompt-length KV landed left-aligned; slot 0 untouched
        for dst, src in zip(jax.tree.leaves(row), jax.tree.leaves(states)):
            np.testing.assert_array_equal(
                np.asarray(dst[:, :5]), np.asarray(src[:, 0]))
        for leaf in jax.tree.leaves(pool.row(0)):
            assert bool(jnp.all(leaf == 0))

    def test_graft_rejects_oversize(self):
        cfg, _ = self._pool()
        b = {"tokens": jnp.zeros((1, 24), jnp.int32)}
        params = api.init(cfg, jax.random.key(7))
        _, states, _ = api.prefill(cfg, params, b)
        with pytest.raises(ValueError):
            SlotCachePool.grow(cfg, states, 1, 16, jnp.float32)  # 24 > 16

    def test_grow_cache_deprecated_shim(self):
        from repro.serving.cache import grow_cache

        cfg, _ = self._pool()
        b = {"tokens": jnp.zeros((1, 5), jnp.int32)}
        params = api.init(cfg, jax.random.key(7))
        _, states, _ = api.prefill(cfg, params, b)
        with pytest.warns(DeprecationWarning):
            grown = grow_cache(cfg, states, 1, 16, jnp.float32)
        ref = SlotCachePool.grow(cfg, states, 1, 16, jnp.float32)
        for a, b_ in zip(jax.tree.leaves(grown), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


class TestSampler:
    def _logits(self, b=4, v=64, seed=0):
        return jnp.asarray(np.random.RandomState(seed).randn(b, v)
                           .astype(np.float32))

    def test_greedy_matches_argmax(self):
        lg = self._logits()
        for policy in (EXACT, GS_FEEDBACK):
            got = sample_tokens(lg, policy=policy)
            np.testing.assert_array_equal(
                np.asarray(got), np.argmax(np.asarray(lg), axis=-1))

    def test_top_k_restricts_support(self):
        lg = self._logits(b=8, v=32)
        topk = 5
        allowed = np.argsort(np.asarray(lg), axis=-1)[:, -topk:]
        for trial in range(20):
            got = np.asarray(sample_tokens(
                lg, policy=GS_FEEDBACK, temperature=1.5, top_k=topk,
                key=jax.random.key(trial)))
            for row in range(lg.shape[0]):
                assert got[row] in allowed[row]

    def test_temperature_vector_mixes_greedy_and_sampled(self):
        lg = self._logits(b=6, v=256, seed=3)
        temps = jnp.asarray([0.0, 1.0, 0.0, 2.0, 0.0, 1.0], jnp.float32)
        greedy = np.argmax(np.asarray(lg), axis=-1)
        draws = [np.asarray(sample_tokens(lg, policy=GS_FEEDBACK,
                                          temperature=temps,
                                          key=jax.random.key(t)))
                 for t in range(30)]
        for d in draws:
            np.testing.assert_array_equal(d[[0, 2, 4]], greedy[[0, 2, 4]])
        # stochastic rows actually vary across keys
        assert len({tuple(d[[1, 3, 5]].tolist()) for d in draws}) > 1

    def test_sampled_distribution_tracks_probs(self):
        """Inverse-CDF through the Goldschmidt softmax: a dominant logit
        must dominate the draws."""
        lg = jnp.asarray([[0.0, 4.0, 0.0, 0.0]], jnp.float32)
        hits = sum(
            int(np.asarray(sample_tokens(lg, policy=GS_FEEDBACK,
                                         temperature=1.0,
                                         key=jax.random.key(i)))[0] == 1)
            for i in range(50))
        assert hits >= 40  # p(top) ~ 0.95


class TestVectorCurIndex:
    """decode_attention/cache_update with a (b,) cur_index must equal
    per-row scalar calls — the layer-level contract the engine rests on."""

    def test_decode_attention_vector_matches_scalar(self):
        from repro.layers import attention as attn

        r = np.random.RandomState(9)
        b, S, h, kh, hd = 3, 16, 4, 2, 8
        q = jnp.asarray(r.randn(b, 1, h, hd).astype(np.float32))
        k = jnp.asarray(r.randn(b, S, kh, hd).astype(np.float32))
        v = jnp.asarray(r.randn(b, S, kh, hd).astype(np.float32))
        cur = jnp.asarray([3, 9, 14], jnp.int32)
        vec = attn.decode_attention(q, k, v, cur, policy=GS_FEEDBACK)
        for i in range(b):
            one = attn.decode_attention(
                q[i:i + 1], k[i:i + 1], v[i:i + 1], jnp.int32(cur[i]),
                policy=GS_FEEDBACK)
            np.testing.assert_allclose(np.asarray(vec[i:i + 1]),
                                       np.asarray(one), atol=1e-6)

    def test_cache_update_vector_matches_scalar(self):
        from repro.layers import attention as attn

        r = np.random.RandomState(10)
        L, b, S, kh, hd = 2, 3, 12, 2, 4
        kc = jnp.asarray(r.randn(L, b, S, kh, hd).astype(np.float32))
        vc = jnp.asarray(r.randn(L, b, S, kh, hd).astype(np.float32))
        kn = jnp.asarray(r.randn(b, 1, kh, hd).astype(np.float32))
        vn = jnp.asarray(r.randn(b, 1, kh, hd).astype(np.float32))
        cur = jnp.asarray([0, 5, 11], jnp.int32)
        k2, v2 = attn.cache_update(kc, vc, 1, kn, vn, cur)
        for i in range(b):
            k1, v1 = attn.cache_update(kc[:, i:i + 1], vc[:, i:i + 1], 1,
                                       kn[i:i + 1], vn[i:i + 1],
                                       jnp.int32(cur[i]))
            np.testing.assert_array_equal(np.asarray(k2[:, i:i + 1]),
                                          np.asarray(k1))
            np.testing.assert_array_equal(np.asarray(v2[:, i:i + 1]),
                                          np.asarray(v1))


def _paged_cfg(n_slots=2, s_max=22, page_size=4, n_pages=0, prefix="exact"):
    return EngineConfig(n_slots=n_slots, s_max=s_max, pool="paged",
                        page_size=page_size, n_pages=n_pages, prefix=prefix)


class TestPagedServing:
    """Paged-vs-slot (and vs sequential) token-for-token greedy parity,
    prefix sharing, and page accounting through the full engine."""

    def test_paged_matches_slot_pool_and_sequential(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(20))
        rng = np.random.RandomState(20)
        reqs = _requests(cfg, rng, [(6, 5, 0.0), (9, 8, 0.0),
                                    (4, 3, 0.02), (7, 6, 0.03)])
        outs_s, _ = Engine(cfg, params, EngineConfig(
            n_slots=2, s_max=22)).run(reqs)
        outs_p, m_p = Engine(cfg, params, _paged_cfg()).run(reqs)
        _assert_parity(cfg, params, reqs, outs_p)
        for r in reqs:
            np.testing.assert_array_equal(outs_s[r.rid].tokens,
                                          outs_p[r.rid].tokens)
        assert m_p.pool["kind"] == "paged"
        assert m_p.pool["pages_in_use"] >= 0

    def test_paged_single_slot_recycling_no_page_leak(self):
        """n_slots=1 churns every request through the same slot; with
        prefix sharing off, every page must return to the free list and
        refcounts must drop to zero (a leak here starves admission)."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(21))
        rng = np.random.RandomState(21)
        reqs = _requests(cfg, rng, [(8, 4, 0.0), (5, 6, 0.0),
                                    (10, 3, 0.0), (6, 5, 0.0)])
        eng = Engine(cfg, params, _paged_cfg(n_slots=1, prefix="off"))
        outs, metrics = eng.run(reqs)
        _assert_parity(cfg, params, reqs, outs)
        pool = metrics.pool
        assert pool["pages_in_use"] == 0           # all pages returned
        assert pool["peak_pages_in_use"] > 0       # ...after real use
        assert pool["prefix_entries"] == 0

    def test_paged_tight_arena_throttles_admission(self):
        """An arena sized for ~one request at a time must still serve
        the whole trace correctly (page-budget admission + eviction)."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(22))
        rng = np.random.RandomState(22)
        reqs = _requests(cfg, rng, [(9, 8, 0.0), (10, 7, 0.0),
                                    (8, 9, 0.0)])
        # pages_per_slot = ceil(22/4) = 6 -> minimum legal arena is 7
        eng = Engine(cfg, params, _paged_cfg(n_slots=3, n_pages=7))
        outs, _ = eng.run(reqs)
        _assert_parity(cfg, params, reqs, outs)

    def test_shared_prompt_prefills_once_across_8_requests(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(23))
        rng = np.random.RandomState(23)
        prompt = rng.randint(0, cfg.vocab, (6,))
        reqs = [Request(rid=i, prompt=prompt, max_new_tokens=5)
                for i in range(8)]
        eng = Engine(cfg, params, _paged_cfg(n_slots=4))
        outs, metrics = eng.run(reqs)
        assert metrics.prefill_skips == 7      # prefilled exactly once
        assert metrics.prefill_tokens == 6
        assert metrics.prefix_hits == 7
        assert metrics.prefix_hit_tokens == 7 * 6
        _assert_parity(cfg, params, reqs, outs)  # sharing is bit-exact

    def test_prefix_off_disables_sharing(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(23))
        rng = np.random.RandomState(23)
        prompt = rng.randint(0, cfg.vocab, (6,))
        reqs = [Request(rid=i, prompt=prompt, max_new_tokens=3)
                for i in range(4)]
        _, metrics = Engine(cfg, params,
                            _paged_cfg(n_slots=2, prefix="off")).run(reqs)
        assert metrics.prefill_skips == 0
        assert metrics.prefill_tokens == 4 * 6

    def test_pages_mode_partial_prefix_resumes_bit_exact(self):
        """share='pages': a partial page-aligned hit attaches the shared
        page chain and resumes chunked prefill from the deepest boundary
        snapshot.  The per-chunk schedule is fixed (independent of total
        prompt length), so a resumed prefill is bit-identical to a cold
        one and the sharer skips the shared chunks' compute entirely."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(24))
        rng = np.random.RandomState(24)
        head = rng.randint(0, cfg.vocab, (8,))  # two full 4-token pages
        tails = [rng.randint(0, cfg.vocab, (3,)) for _ in range(2)]
        reqs = [Request(rid=i, prompt=np.concatenate([head, t]),
                        max_new_tokens=4) for i, t in enumerate(tails)]
        # cold reference: each request alone on a fresh pages-mode engine
        cold = [Engine(cfg, params,
                       _paged_cfg(n_slots=2, prefix="pages")).run([r])[r.rid]
                for r in reqs]
        outs, metrics = Engine(cfg, params,
                               _paged_cfg(n_slots=2,
                                          prefix="pages")).run(reqs)
        for r, ref in zip(reqs, cold):
            np.testing.assert_array_equal(ref.tokens, outs[r.rid].tokens)
            assert outs[r.rid].finish_reason == ref.finish_reason
        assert metrics.prefix_hits == 1           # second shares 2 pages
        assert metrics.prefix_hit_tokens == 8
        assert metrics.pool["resume_hits"] == 1
        assert metrics.pool["resume_tokens"] == 8
        # the sharer prefilled only its 3-token private tail
        assert metrics.prefill_tokens == 11 + 3

    @pytest.mark.slow
    @pytest.mark.parametrize("arch,over", [
        ("falcon-mamba-7b", {}),
        ("jamba-1.5-large-398b", {"capacity_factor": 8.0}),
        ("qwen2-vl-72b", {}),
        ("whisper-large-v3", {}),
    ])
    def test_paged_families_parity(self, arch, over):
        """SSM (slot-resident states), hybrid, mrope VLM and encdec
        (cross-KV stays slot-indexed) through the paged decode path."""
        cfg = configs.get_smoke(arch, **F32, **over)
        params = api.init(cfg, jax.random.key(25))
        rng = np.random.RandomState(25)
        reqs = _requests(cfg, rng, [(4, 3, 0.0), (7, 5, 0.0), (10, 4, 0.0)])
        outs, _ = Engine(cfg, params, _paged_cfg()).run(reqs)
        _assert_parity(cfg, params, reqs, outs)

    def test_paged_stochastic_matches_sequential(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(26))
        rng = np.random.RandomState(26)
        reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (s,)),
                        max_new_tokens=g,
                        sampling=SamplingParams(temperature=t, top_k=k))
                for i, (s, g, t, k) in enumerate([
                    (6, 5, 0.9, 8), (9, 6, 0.0, 0), (4, 5, 1.2, 3)])]
        eng = Engine(cfg, params, dataclasses.replace(_paged_cfg(), seed=3))
        outs, _ = eng.run(reqs)
        for r in reqs:
            ref = generate_sequential(cfg, params, r, seed=3)
            np.testing.assert_array_equal(np.asarray(ref),
                                          outs[r.rid].tokens)

    def test_impossible_request_rejected_not_hung(self):
        """A request that can never fit the arena must be rejected up
        front (and the admission loop has a deadlock guard behind it),
        never spun forever."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(27))
        # needs ceil((10+9-1)/4) = 5 pages > the 3 usable in a 4-page arena
        with pytest.raises(ValueError):
            Engine(cfg, params,
                   _paged_cfg(n_slots=1, n_pages=4)).run(
                [Request(rid=0, prompt=np.zeros(10, np.int32),
                         max_new_tokens=9)])

    def test_early_stop_strands_no_pages_and_boosts_concurrency(self):
        """Regression for worst-case over-reservation: a request that
        stops far short of its generation budget must only ever hold the
        pages it wrote (cumulative reserved == written), and a trace the
        worst-case budget forced to run one-at-a-time now runs
        concurrently on the same arena."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(28))
        rng = np.random.RandomState(28)
        prompts = [rng.randint(0, cfg.vocab, (4,)) for _ in range(2)]
        # each stream stops at the first greedy token that has not
        # occurred before and sits at index >= 2: a few of the 18 budgeted
        # tokens, so fewer than the 6 worst-case pages get written, and
        # never the first token (which would end before any overlap)
        def stop_token(p):
            s = [int(t) for t in np.asarray(generate_sequential(
                cfg, params,
                Request(rid=9, prompt=p, max_new_tokens=18), s_max=22))]
            return next(t for j, t in enumerate(s) if j >= 2
                        and t not in s[:j])

        stops = [stop_token(p) for p in prompts]

        def trace():
            return [Request(rid=i, prompt=p, max_new_tokens=18,
                            sampling=SamplingParams(stop=stops[i]))
                    for i, p in enumerate(prompts)]

        # worst-case budget is 6 pages per request; the 7-usable-page
        # arena fits one such reservation at a time
        ecfg = dataclasses.replace(
            _paged_cfg(n_slots=2, n_pages=8, prefix="off"),
            max_prefill_per_tick=2)
        outs_w, m_w = Engine(cfg, params, dataclasses.replace(
            ecfg, page_reserve="worst")).run(trace())
        outs, m = Engine(cfg, params, ecfg).run(trace())
        for i in range(2):
            assert outs[i].finish_reason == FINISH_STOP
            np.testing.assert_array_equal(outs_w[i].tokens, outs[i].tokens)
        # same arena, same trace: prompt-reservation overlaps the
        # requests the whole-lifetime budget serialized
        assert m_w.peak_active == 1
        assert m.peak_active == 2
        st = m.pool
        assert st["reserved_pages"] == st["written_pages"]  # no stranding
        assert st["pages_in_use"] == 0
        st_w = m_w.pool
        assert st_w["written_pages"] < st_w["reserved_pages"]  # the bug


class TestPagedCachePool:
    """Host-side page accounting: refcounts, COW, eviction, trash page."""

    def _pool(self, **kw):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        kw.setdefault("page_size", 4)
        kw.setdefault("n_slots", 2)
        kw.setdefault("n_pages", 0)
        n_slots = kw.pop("n_slots")
        return cfg, PagedCachePool(cfg, n_slots, 16, jnp.float32, **kw)

    def _write(self, cfg, pool, slot, req):
        params = getattr(self, "_params", None)
        if params is None:
            params = self._params = api.init(cfg, jax.random.key(30))
        from repro.serving import prefill_batch

        logits, states, _ = api.prefill(cfg, params, prefill_batch(cfg, req))
        pool.write(int(slot), states, req=req, logits=logits)

    def test_alloc_reserves_prompt_pages_and_appends_grow(self):
        cfg, pool = self._pool()
        req = Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                      max_new_tokens=6)  # prompt 5 -> 2 pages (worst: 3)
        before = pool.pages_in_use
        slot = pool.alloc(req)
        assert pool.pages_in_use == before + 2  # prompt footprint only
        assert all(pool.ref[p] == 1 for p in pool._slot_pages[int(slot)])
        # decode growth: ensure_page appends exactly at page boundaries
        assert pool.ensure_page(int(slot), 5)   # pos 5 fits reserved pages
        assert pool.pages_in_use == before + 2
        assert pool.ensure_page(int(slot), 8)   # pos 8 -> third page
        assert pool.pages_in_use == before + 3
        assert pool.appended_pages == 1
        self._write(cfg, pool, int(slot), req)
        pool.free(int(slot))
        # the prefix entry registered at write keeps the 2 prompt pages
        assert pool.pages_in_use == 2
        pool.clear_prefix()
        assert pool.pages_in_use == 0
        assert int(pool.ref.sum()) == 1  # only the pinned trash page

    def test_worst_reserve_mode_keeps_legacy_budget(self):
        cfg, pool = self._pool(reserve="worst")
        req = Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                      max_new_tokens=6)  # 10 positions -> 3 pages up front
        slot = pool.alloc(req)
        assert pool.pages_in_use == 3
        assert pool.stats()["reserve"] == "worst"
        # growth within the reservation is a no-op
        assert pool.ensure_page(int(slot), 9)
        assert pool.appended_pages == 0

    def test_append_page_fails_cleanly_when_arena_full(self):
        cfg, pool = self._pool(n_slots=2, n_pages=5, share="off")
        r0 = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                     max_new_tokens=8)
        s0 = pool.alloc(r0)  # 3 prompt pages, 1 free
        r1 = Request(rid=1, prompt=np.arange(3, dtype=np.int32),
                     max_new_tokens=8)
        pool.alloc(r1)       # 1 prompt page, 0 free
        # nothing evictable (share="off") -> append must refuse, not raise
        assert pool.append_page(int(s0)) is False
        assert pool.ensure_page(int(s0), 9) is True    # within reserved
        assert pool.ensure_page(int(s0), 12) is False  # needs a 4th page
        st = pool.stats()
        assert st["reserved_pages"] == 4 and st["appended_pages"] == 0

    def test_trash_page_never_freed_and_freed_rows_point_at_it(self):
        cfg, pool = self._pool()
        req = Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                      max_new_tokens=2)
        slot = pool.alloc(req)
        assert 0 not in pool._slot_pages[int(slot)]
        self._write(cfg, pool, int(slot), req)
        pool.free(int(slot))
        assert (pool.table[int(slot)] == 0).all()
        assert pool.ref[0] == 1

    def test_exact_hit_skips_prefill_and_cow_copies_tail(self):
        cfg, pool = self._pool(n_slots=2)
        req = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                      max_new_tokens=4)
        s0 = pool.alloc(req)
        assert not s0.hit.skip_prefill
        self._write(cfg, pool, int(s0), req)
        req2 = Request(rid=1, prompt=np.arange(6, dtype=np.int32),
                       max_new_tokens=4)
        s1 = pool.alloc(req2)
        assert s1.hit.skip_prefill
        assert pool.cow_copies == 1  # boundary page copied for writing
        # full prompt page is shared, tail is private
        assert pool.table[int(s1), 0] == pool.table[int(s0), 0]
        assert pool.table[int(s1), 1] != pool.table[int(s0), 1]

    def test_read_only_sharer_attaches_tail_without_cow(self):
        cfg, pool = self._pool(n_slots=2)
        req = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                      max_new_tokens=4)
        s0 = pool.alloc(req)
        self._write(cfg, pool, int(s0), req)
        req2 = Request(rid=1, prompt=np.arange(6, dtype=np.int32),
                       max_new_tokens=1)  # never writes -> no COW needed
        s1 = pool.alloc(req2)
        assert s1.hit.skip_prefill and pool.cow_copies == 0
        assert pool.table[int(s1), 1] == pool.table[int(s0), 1]

    def test_eviction_frees_cold_entries_but_never_slot_pages(self):
        cfg, pool = self._pool(n_slots=1, n_pages=7)
        # fill the index with two dead entries (slot freed, entry kept)
        for rid, ln in ((0, 5), (1, 9)):
            req = Request(rid=rid,
                          prompt=np.full(ln, rid, np.int32),
                          max_new_tokens=2)
            s = pool.alloc(req)
            self._write(cfg, pool, int(s), req)
            pool.free(int(s))
        assert len(pool._index) == 2 and pool.pages_in_use > 0
        # a big request forces eviction of the LRU entries
        big = Request(rid=2, prompt=np.arange(12, dtype=np.int32),
                      max_new_tokens=5)
        assert pool.can_admit(big)
        s = pool.alloc(big)
        assert pool.evictions > 0
        assert len(pool._slot_pages[int(s)]) == 3  # ceil(12/4) prompt pages

    def test_can_admit_accounts_for_page_budget(self):
        cfg, pool = self._pool(n_slots=2, n_pages=5, share="off")
        r0 = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                     max_new_tokens=8)   # 9-token prompt -> 3 pages
        assert pool.can_admit(r0)
        s0 = pool.alloc(r0)
        r1 = Request(rid=1, prompt=np.arange(9, dtype=np.int32),
                     max_new_tokens=8)
        assert not pool.can_admit(r1)    # 3 more pages > 1 free
        self._write(cfg, pool, int(s0), r0)
        pool.free(int(s0))
        assert pool.can_admit(r1)

    def test_alloc_requires_request(self):
        _, pool = self._pool()
        with pytest.raises(ValueError):
            pool.alloc()

    def test_row_gathers_dense_view(self):
        cfg, pool = self._pool()
        req = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                      max_new_tokens=2)
        s = pool.alloc(req)
        self._write(cfg, pool, int(s), req)
        row = pool.row(int(s))
        for leaf in jax.tree.leaves(row):
            assert leaf.shape[1] == 16  # s_max-length dense view


class TestSamplingParamsAPI:
    def test_temperature_kwarg_shim_populates_sampling(self):
        with pytest.warns(DeprecationWarning, match="SamplingParams"):
            r = Request(rid=0, prompt=np.zeros(4, np.int32),
                        max_new_tokens=2, temperature=0.7)
        assert r.sampling.temperature == 0.7
        assert r.sampling.stochastic

    def test_sampling_params_route_does_not_warn(self):
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("error", DeprecationWarning)
            r = Request(rid=0, prompt=np.zeros(4, np.int32),
                        max_new_tokens=2,
                        sampling=SamplingParams(temperature=0.7))
        assert r.temperature == 0.7  # mirror stays consistent

    def test_conflicting_kwarg_and_sampling_rejected(self):
        with pytest.raises(ValueError):
            Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=2,
                    temperature=0.7,
                    sampling=SamplingParams(temperature=0.2))

    def test_sampling_params_validate(self):
        with pytest.raises(ValueError):
            SamplingParams(temperature=-1.0)
        with pytest.raises(ValueError):
            SamplingParams(top_k=-2)

    def test_stop_token_sets_finish_reason(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(31))
        rng = np.random.RandomState(31)
        prompt = rng.randint(0, cfg.vocab, (6,))
        free = generate_sequential(
            cfg, params, Request(rid=0, prompt=prompt, max_new_tokens=6))
        assert free.finish_reason == "length"
        stop = int(np.asarray(free)[1])
        req = Request(rid=0, prompt=prompt, max_new_tokens=6,
                      sampling=SamplingParams(stop=stop))
        outs, _ = Engine(cfg, params, EngineConfig(n_slots=1)).run([req])
        got = outs[0]
        assert got.finish_reason == "stop"
        assert got.tokens[-1] == stop
        assert len(got.tokens) < 6
        seq = generate_sequential(cfg, params, req)
        assert seq.finish_reason == "stop"
        np.testing.assert_array_equal(seq.tokens, got.tokens)

    def test_serve_result_unpacks_and_maps(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(32))
        reqs = [Request(rid=5, prompt=np.zeros(4, np.int32),
                        max_new_tokens=2)]
        res = Engine(cfg, params, EngineConfig(n_slots=1)).run(reqs)
        outs, metrics = res                      # legacy 2-tuple protocol
        assert 5 in outs and metrics.n_requests == 1
        assert res[5].tokens.shape == (2,)       # mapping protocol
        assert sorted(res.keys()) == [5]
        assert res[5].finish_reason == "length"

    def test_per_request_top_k_mixes_in_one_tick(self):
        """Rows with different top_k in the same fused tick must each
        match their own sequential reference (per-row kth threshold)."""
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(33))
        rng = np.random.RandomState(33)
        reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (s,)),
                        max_new_tokens=5,
                        sampling=SamplingParams(temperature=0.9, top_k=k))
                for i, (s, k) in enumerate([(6, 2), (8, 0), (5, 9)])]
        eng = Engine(cfg, params, EngineConfig(n_slots=3, seed=5))
        outs, _ = eng.run(reqs)
        for r in reqs:
            ref = generate_sequential(cfg, params, r, seed=5)
            np.testing.assert_array_equal(np.asarray(ref),
                                          outs[r.rid].tokens)


class TestRequestValidation:
    def test_bad_requests_rejected(self):
        with pytest.raises(ValueError):
            Request(rid=0, prompt=np.zeros(0, np.int32), max_new_tokens=2)
        with pytest.raises(ValueError):
            Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=0)

    def test_overlong_request_rejected_at_run(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(8))
        eng = Engine(cfg, params, EngineConfig(n_slots=1, s_max=16))
        req = Request(rid=0, prompt=np.zeros(10, np.int32),
                      max_new_tokens=10)
        with pytest.raises(ValueError):
            eng.run([req])

    def test_duplicate_rids_rejected(self):
        cfg = configs.get_smoke("tinyllama-1.1b", **F32)
        params = api.init(cfg, jax.random.key(8))
        eng = Engine(cfg, params, EngineConfig(n_slots=1))
        reqs = [Request(rid=7, prompt=np.zeros(4, np.int32),
                        max_new_tokens=2) for _ in range(2)]
        with pytest.raises(ValueError):
            eng.run(reqs)

    def test_encdec_requires_frames(self):
        cfg = configs.get_smoke("whisper-large-v3", **F32)
        params = api.init(cfg, jax.random.key(9))
        eng = Engine(cfg, params, EngineConfig(n_slots=1))
        with pytest.raises(ValueError):
            eng.run([Request(rid=0, prompt=np.zeros(4, np.int32),
                             max_new_tokens=2)])
