"""Chaos harness for the fault-tolerant serving runtime.

Every fault class the engine claims to contain (engine.py "Fault
tolerance"; serving/resilience.py for the containment model) is driven
here through :class:`ServeFaultInjector` scripts, and each test asserts
the full containment contract:

* the faulted request finishes with the right ``finish_reason``,
* its slot / pages / prefix refcounts are reclaimed exactly
  (``metrics.pool`` stats match a fault-free run),
* unaffected co-scheduled requests stay **bit-identical** to the
  fault-free run (greedy fp32),
* the failure counters on :class:`ServeMetrics` account for the event.

Engines with an injector call ``warmup`` only with a ``reset()`` of the
injector after it — the warm-up runs the same loop and would consume the
script.
"""

import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops
from repro.kernels.tuning import dispatch
from repro.models import api
from repro.serving import (AdmissionError, Engine, EngineConfig,
                           FINISH_CANCELLED, FINISH_DEADLINE, FINISH_LENGTH,
                           FINISH_NUMERIC, FINISH_REJECTED, Request,
                           SamplingParams, ServeFaultInjector, ServeMetrics,
                           TickFailure, generate_sequential,
                           poison_slot_cache)

F32 = dict(dtype="float32", param_dtype="float32")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("tinyllama-1.1b", **F32)
    params = api.init(cfg, jax.random.key(0))
    return cfg, params


def _requests(cfg, rng, specs, **sampling_kw):
    sp = SamplingParams(**sampling_kw) if sampling_kw else None
    return [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (s,)),
                    max_new_tokens=g, arrival_time=t, sampling=sp)
            for i, (s, g, t) in enumerate(specs)]


def _slots_reclaimed(metrics):
    """Every slot (and page, for paged pools) is free at run end."""
    st = metrics.pool
    assert st["free_slots"] == st["n_slots"], st
    if st.get("kind") == "paged":
        assert st["seized_pages"] == 0, st


class TestNumericQuarantine:
    """NaN poison in one slot: that request fails with
    finish_reason="numeric_error", everyone else keeps exact parity."""

    @pytest.mark.parametrize("pool", ["slot", "paged"])
    def test_poisoned_slot_quarantined_others_bit_identical(self, model,
                                                            pool):
        cfg, params = model
        rng = np.random.RandomState(0)
        specs = [(6, 6, 0.0), (9, 8, 0.0), (4, 6, 0.0)]
        kw = dict(pool=pool, page_size=4, n_pages=24) if pool == "paged" \
            else {}
        base = Engine(cfg, params, EngineConfig(n_slots=3, **kw))
        outs0, m0 = base.run(_requests(cfg, rng, specs))

        inj = ServeFaultInjector(poison={2: (1,)})
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=3, injector=inj, **kw))
        outs, m = eng.run(_requests(cfg, np.random.RandomState(0), specs))

        assert outs[1].finish_reason == FINISH_NUMERIC
        assert len(outs[1].tokens) < len(outs0[1].tokens)
        np.testing.assert_array_equal(outs0[0].tokens, outs[0].tokens)
        np.testing.assert_array_equal(outs0[2].tokens, outs[2].tokens)
        assert m.failed == 1 and m0.failed == 0
        _slots_reclaimed(m)

    def test_single_slot_recycles_after_quarantine(self, model):
        """n_slots=1: the quarantined slot must be clean for the next
        request through the SAME slot."""
        cfg, params = model
        rng = np.random.RandomState(1)
        specs = [(8, 6, 0.0), (5, 6, 0.0), (10, 4, 0.0)]
        inj = ServeFaultInjector(poison={1: (0,)})
        eng = Engine(cfg, params, EngineConfig(n_slots=1, injector=inj))
        reqs = _requests(cfg, rng, specs)
        outs, m = eng.run(reqs)
        assert outs[0].finish_reason == FINISH_NUMERIC
        for r in reqs[1:]:
            ref = generate_sequential(cfg, params, r)
            np.testing.assert_array_equal(ref, outs[r.rid].tokens)
            assert outs[r.rid].finish_reason == FINISH_LENGTH
        _slots_reclaimed(m)

    def test_guard_off_matches_guard_on_tokens(self, model):
        """The guard changes the tick's return arity, never its tokens."""
        cfg, params = model
        rng = np.random.RandomState(2)
        specs = [(6, 5, 0.0), (9, 7, 0.0)]
        on = Engine(cfg, params, EngineConfig(n_slots=2,
                                              numeric_guard=True))
        off = Engine(cfg, params, EngineConfig(n_slots=2,
                                               numeric_guard=False))
        o1, _ = on.run(_requests(cfg, rng, specs))
        o2, _ = off.run(_requests(cfg, np.random.RandomState(2), specs))
        for rid in (0, 1):
            np.testing.assert_array_equal(o1[rid].tokens, o2[rid].tokens)

    def test_poison_int8_arena_raises(self, model):
        """int8 KV has no NaN encoding: poisoning must refuse loudly
        instead of silently writing garbage."""
        import dataclasses as dc
        cfg, params = model
        cfg_q = dc.replace(cfg, quant="int8")
        eng = Engine(cfg_q, params, EngineConfig(n_slots=2))
        pool = eng._make_pool()
        pool.alloc(Request(rid=0, prompt=np.arange(4), max_new_tokens=2))
        with pytest.raises(ValueError, match="non-float"):
            poison_slot_cache(pool, 0)


class TestDeadlines:
    def test_skew_expires_mid_decode_partial_tokens_kept(self, model):
        cfg, params = model
        rng = np.random.RandomState(3)
        reqs = _requests(cfg, rng, [(6, 10, 0.0), (9, 10, 0.0)],
                         deadline_ms=5000.0)
        inj = ServeFaultInjector(skew={3: 100.0})
        eng = Engine(cfg, params, EngineConfig(n_slots=2, injector=inj))
        # compile first: the 5 s deadline is for serving, and compiling
        # on a loaded machine can outlast it before tick 3's skew lands
        eng.warmup([6, 9])
        inj.reset()
        outs, m = eng.run(reqs)
        for rid in (0, 1):
            assert outs[rid].finish_reason == FINISH_DEADLINE
            assert 0 < len(outs[rid].tokens) < 10  # partial kept
        assert m.timed_out == 2
        _slots_reclaimed(m)

    def test_queued_request_expires_with_zero_tokens(self, model):
        cfg, params = model
        rng = np.random.RandomState(4)
        r0 = Request(rid=0, prompt=rng.randint(0, cfg.vocab, (6,)),
                     max_new_tokens=10)  # no deadline
        r1 = Request(rid=1, prompt=rng.randint(0, cfg.vocab, (5,)),
                     max_new_tokens=4,
                     sampling=SamplingParams(deadline_ms=5000.0))
        inj = ServeFaultInjector(skew={2: 100.0})
        eng = Engine(cfg, params, EngineConfig(n_slots=1, injector=inj))
        outs, m = eng.run([r0, r1])
        assert outs[0].finish_reason == FINISH_LENGTH  # inf deadline
        assert outs[1].finish_reason == FINISH_DEADLINE
        assert len(outs[1].tokens) == 0 and outs[1].ttft_s == 0.0
        assert m.timed_out == 1
        _slots_reclaimed(m)

    def test_backoff_requeued_pending_expires_with_deadline(self, model):
        """A request bounced back to pending by queue backpressure must
        still expire with finish_reason="deadline" (not retry toward
        "rejected"), and its trace chain must close with that reason."""
        from repro.obs import Tracer
        from repro.obs.export import request_chains, validate_chains

        cfg, params = model
        rng = np.random.RandomState(16)
        r0 = Request(rid=0, prompt=rng.randint(0, cfg.vocab, (6,)),
                     max_new_tokens=10)  # occupies the only slot
        r1 = Request(rid=1, prompt=rng.randint(0, cfg.vocab, (5,)),
                     max_new_tokens=4)   # fills the bounded queue
        r2 = Request(rid=2, prompt=rng.randint(0, cfg.vocab, (4,)),
                     max_new_tokens=4,
                     sampling=SamplingParams(deadline_ms=5000.0))
        tr = Tracer()
        inj = ServeFaultInjector(skew={3: 100.0})
        eng = Engine(cfg, params, EngineConfig(
            n_slots=1, max_queue=1, max_retries=500,
            retry_backoff_s=0.001, injector=inj, tracer=tr))
        outs, m = eng.run([r0, r1, r2])
        assert outs[2].finish_reason == FINISH_DEADLINE
        assert len(outs[2].tokens) == 0
        assert outs[0].finish_reason == FINISH_LENGTH
        assert outs[1].finish_reason == FINISH_LENGTH
        assert m.timed_out == 1 and m.retried > 0
        expect = {r.rid: outs[r.rid].finish_reason for r in (r0, r1, r2)}
        assert validate_chains(tr, expect) == []
        # rid 2 was in the backoff cycle when it expired
        insts = request_chains(tr)[2]["instants"]
        assert "retry_backoff" in insts and insts[-1] == "finish"
        _slots_reclaimed(m)

    def test_sequential_deadline_semantics_match(self, model):
        cfg, params = model
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, cfg.vocab, (6,))
        expired = generate_sequential(
            cfg, params, Request(rid=0, prompt=prompt, max_new_tokens=5,
                                 sampling=SamplingParams(deadline_ms=1e-4)))
        assert expired.finish_reason == FINISH_DEADLINE
        assert len(expired.tokens) == 0
        fine = generate_sequential(
            cfg, params, Request(rid=0, prompt=prompt, max_new_tokens=5,
                                 sampling=SamplingParams(deadline_ms=6e4)))
        assert fine.finish_reason == FINISH_LENGTH
        assert len(fine.tokens) == 5


class TestCancellation:
    def test_cancel_active_releases_others_keep_parity(self, model):
        cfg, params = model
        rng = np.random.RandomState(6)
        specs = [(6, 8, 0.0), (9, 8, 0.0), (4, 8, 0.0)]
        base = Engine(cfg, params, EngineConfig(n_slots=3))
        outs0, _ = base.run(_requests(cfg, rng, specs))
        inj = ServeFaultInjector(cancels={2: (1,)})
        eng = Engine(cfg, params, EngineConfig(n_slots=3, injector=inj))
        outs, m = eng.run(_requests(cfg, np.random.RandomState(6), specs))
        assert outs[1].finish_reason == FINISH_CANCELLED
        assert 0 < len(outs[1].tokens) < 8
        np.testing.assert_array_equal(outs0[0].tokens, outs[0].tokens)
        np.testing.assert_array_equal(outs0[2].tokens, outs[2].tokens)
        assert m.cancelled == 1
        _slots_reclaimed(m)

    @pytest.mark.parametrize("n_slots", [1, 3])
    def test_cancel_prefix_sharer_refcounts_and_index_intact(self, model,
                                                             n_slots):
        """Paged pool with prefix="exact": cancelling one sharer
        mid-decode must return its page refs to baseline, leave the
        prefix index serving later identical prompts, and not perturb
        the surviving sharers' tokens."""
        cfg, params = model
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, cfg.vocab, (8,))
        ecfg = dict(n_slots=n_slots, pool="paged", page_size=4, n_pages=24,
                    prefix="exact")

        def sharers():
            # rid 2 arrives late: it must still exact-hit the prefix
            # index AFTER rid 1 was cancelled
            return [Request(rid=i, prompt=prompt, max_new_tokens=6,
                            arrival_time=(0.2 if i == 2 else 0.0))
                    for i in range(3)]

        base = Engine(cfg, params, EngineConfig(**ecfg))
        outs0, m0 = base.run(sharers())

        inj = ServeFaultInjector(cancels={2: (1,)})
        eng = Engine(cfg, params, EngineConfig(injector=inj, **ecfg))
        outs, m = eng.run(sharers())

        assert outs[1].finish_reason == FINISH_CANCELLED
        for rid in (0, 2):
            np.testing.assert_array_equal(outs0[rid].tokens,
                                          outs[rid].tokens)
            assert outs[rid].finish_reason == outs0[rid].finish_reason
        # the late sharer still exact-hit the index post-cancel
        assert m.prefill_skips >= 1
        # refcount baseline: the fault-free and cancelled runs end with
        # the identical arena occupancy (requests freed, index entries
        # holding the same shared pages)
        assert m.pool["free_pages"] == m0.pool["free_pages"]
        assert m.pool["seized_pages"] == 0
        _slots_reclaimed(m)


class TestRetryAndBackpressure:
    def test_tick_failure_retries_to_parity(self, model):
        cfg, params = model
        rng = np.random.RandomState(8)
        specs = [(6, 5, 0.0), (9, 7, 0.0)]
        base = Engine(cfg, params, EngineConfig(n_slots=2))
        outs0, _ = base.run(_requests(cfg, rng, specs))
        inj = ServeFaultInjector(fail_ticks=(1,))
        eng = Engine(cfg, params, EngineConfig(n_slots=2, injector=inj))
        outs, m = eng.run(_requests(cfg, np.random.RandomState(8), specs))
        for rid in (0, 1):
            np.testing.assert_array_equal(outs0[rid].tokens,
                                          outs[rid].tokens)
        assert m.retried >= 1
        _slots_reclaimed(m)

    def test_tick_failure_exhausts_budget_and_raises(self, model):
        cfg, params = model
        rng = np.random.RandomState(9)
        inj = ServeFaultInjector(fail_ticks=(1, 1, 1))
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=2, max_retries=2,
                                  retry_backoff_s=0.001, injector=inj))
        with pytest.raises(TickFailure):
            eng.run(_requests(cfg, rng, [(6, 5, 0.0)]))

    def test_bounded_queue_rejects_when_retries_exhausted(self, model):
        cfg, params = model
        rng = np.random.RandomState(10)
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=1, max_queue=1, max_retries=0))
        outs, m = eng.run(_requests(
            cfg, rng, [(6, 4, 0.0), (5, 4, 0.0), (4, 4, 0.0)]))
        reasons = [outs[i].finish_reason for i in range(3)]
        assert reasons.count(FINISH_REJECTED) == 2
        rejected = [i for i in range(3)
                    if outs[i].finish_reason == FINISH_REJECTED]
        assert all(len(outs[i].tokens) == 0 for i in rejected)
        assert m.failed == 2
        _slots_reclaimed(m)

    def test_bounded_queue_retry_backoff_completes_all(self, model):
        cfg, params = model
        rng = np.random.RandomState(11)
        reqs = _requests(cfg, rng,
                         [(6, 4, 0.0), (5, 4, 0.0), (4, 4, 0.0)])
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=1, max_queue=1, max_retries=50,
                                  retry_backoff_s=0.001))
        outs, m = eng.run(reqs)
        for r in reqs:
            assert outs[r.rid].finish_reason == FINISH_LENGTH
            ref = generate_sequential(cfg, params, r)
            np.testing.assert_array_equal(ref, outs[r.rid].tokens)
        assert m.retried >= 1 and m.failed == 0
        _slots_reclaimed(m)


class TestPreemptionOverDeadlock:
    def test_overcommitted_arena_preempts_and_replays_exactly(self, model):
        """Two requests whose page budgets cannot coexist: the engine
        preempts the youngest instead of deadlocking, and the replayed
        request's tokens are bit-identical to an uncontended run."""
        cfg, params = model
        rng = np.random.RandomState(12)
        r0 = Request(rid=0, prompt=rng.randint(0, cfg.vocab, (4,)),
                     max_new_tokens=9)   # 3 pages
        r1 = Request(rid=1, prompt=rng.randint(0, cfg.vocab, (8,)),
                     max_new_tokens=9, arrival_time=0.01)  # 4 pages
        eng = Engine(cfg, params, EngineConfig(
            n_slots=2, s_max=16, pool="paged", page_size=4, n_pages=6,
            preempt_after_ticks=2, prefix="off"))
        outs, m = eng.run([r0, r1])
        assert m.preempted >= 1
        for r in (r0, r1):
            ref = generate_sequential(cfg, params, r, s_max=16)
            np.testing.assert_array_equal(ref, outs[r.rid].tokens)
            assert outs[r.rid].finish_reason == FINISH_LENGTH
        assert m.pool["free_pages"] == m.pool["n_pages"] - 1  # trash pinned
        _slots_reclaimed(m)

    def test_stochastic_replay_is_scheduler_invariant(self, model):
        """Preemption + replay must not perturb a stochastic stream:
        the (rid, absolute position) PRNG keying replays exactly."""
        cfg, params = model
        rng = np.random.RandomState(13)
        sp = SamplingParams(temperature=0.8, top_k=8)
        r0 = Request(rid=0, prompt=rng.randint(0, cfg.vocab, (4,)),
                     max_new_tokens=9, sampling=sp)
        r1 = Request(rid=1, prompt=rng.randint(0, cfg.vocab, (8,)),
                     max_new_tokens=9, arrival_time=0.01, sampling=sp)
        eng = Engine(cfg, params, EngineConfig(
            n_slots=2, s_max=16, pool="paged", page_size=4, n_pages=6,
            preempt_after_ticks=2, prefix="off"))
        outs, m = eng.run([r0, r1])
        assert m.preempted >= 1
        wide = Engine(cfg, params, EngineConfig(n_slots=2, s_max=16,
                                                pool="paged", page_size=4,
                                                prefix="off"))
        outs_w, m_w = wide.run([r0, r1])
        assert m_w.preempted == 0
        for rid in (0, 1):
            np.testing.assert_array_equal(outs_w[rid].tokens,
                                          outs[rid].tokens)


class TestAdmissionError:
    def test_attributes_and_message(self):
        err = AdmissionError(7, {"kind": "paged", "n_pages": 6,
                                 "free_pages": 1, "free_slots": 2,
                                 "page_size": 4, "seized_pages": 4,
                                 "prefix_hits": 0},
                             queued=[7, 9], pages_needed={7: 3, 9: 2})
        assert isinstance(err, RuntimeError)
        assert err.rid == 7
        assert err.queued == [7, 9]
        assert err.pages_needed == {7: 3, 9: 2}
        assert err.pool_stats["free_pages"] == 1
        msg = str(err)
        assert "request 7 cannot be admitted" in msg
        assert "free_pages" in msg and "queued rids: [7, 9]" in msg
        assert "pages needed" in msg
        assert "prefix_hits" not in msg  # noise keys filtered

    def test_squeezed_arena_raises_typed_error(self, model):
        cfg, params = model
        rng = np.random.RandomState(14)
        inj = ServeFaultInjector(squeeze={0: 4})  # 5 usable -> 1 free
        eng = Engine(cfg, params, EngineConfig(
            n_slots=2, s_max=16, pool="paged", page_size=4, n_pages=6,
            prefix="off", injector=inj))
        req = Request(rid=7, prompt=rng.randint(0, cfg.vocab, (4,)),
                      max_new_tokens=9)
        with pytest.raises(AdmissionError) as ei:
            eng.run([req])
        assert ei.value.rid == 7
        # prompt-footprint admission succeeds on the one unseized page;
        # the typed error now surfaces at the first decode-time append,
        # still naming the request and the (1-page) shortfall
        assert ei.value.pages_needed == {7: 1}
        assert ei.value.pool_stats["seized_pages"] == 4

    def test_squeeze_then_release_recovers(self, model):
        cfg, params = model
        rng = np.random.RandomState(15)
        req = Request(rid=0, prompt=rng.randint(0, cfg.vocab, (4,)),
                      max_new_tokens=9, arrival_time=0.05)
        inj = ServeFaultInjector(squeeze={0: 4}, release_ticks=(1,))
        # a second request keeps the loop ticking while rid 0 is stuck
        pad = Request(rid=1, prompt=rng.randint(0, cfg.vocab, (4,)),
                      max_new_tokens=9)
        eng = Engine(cfg, params, EngineConfig(
            n_slots=2, s_max=16, pool="paged", page_size=4, n_pages=10,
            prefix="off", injector=inj))
        outs, m = eng.run([pad, req])
        for r in (pad, req):
            ref = generate_sequential(cfg, params, r, s_max=16)
            np.testing.assert_array_equal(ref, outs[r.rid].tokens)
        assert m.pool["seized_pages"] == 0
        _slots_reclaimed(m)


class TestKernelFallback:
    def test_failed_kernel_downgrades_to_reference(self, monkeypatch):
        x = np.linspace(0.5, 2.0, 8).astype(np.float32)
        ref = np.asarray(ops.gs_recip(x))
        dispatch.reset_fallback_stats()

        def boom(*a, **k):
            raise RuntimeError("injected kernel fault")

        monkeypatch.setattr(ops, "_gs_recip", boom)
        monkeypatch.setattr(dispatch, "_fallback_override", True)  # opt in
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            got = np.asarray(ops.gs_recip(x))
        np.testing.assert_allclose(got, ref, rtol=1e-6)
        assert dispatch.fallback_stats().get("gs_recip") == 1
        assert dispatch.fallback_total() >= 1
        assert any("downgrading to the jnp reference" in str(x.message)
                   for x in w)
        dispatch.reset_fallback_stats()

    def test_fallback_disabled_propagates(self, monkeypatch):
        def boom(*a, **k):
            raise RuntimeError("injected kernel fault")

        monkeypatch.setattr(ops, "_gs_recip", boom)
        monkeypatch.delenv(dispatch.ENV_FALLBACK, raising=False)
        dispatch.reset_fallback_stats()
        # off by default, and when switched off explicitly
        for on in (None, False):
            dispatch.enable_fallback(on)
            try:
                with pytest.raises(RuntimeError, match="injected kernel"):
                    ops.gs_recip(np.ones(4, np.float32))
            finally:
                dispatch.enable_fallback(None)
        assert dispatch.fallback_total() == 0
        dispatch.reset_fallback_stats()


class TestMetricsSurface:
    def test_failure_counters_in_to_dict(self):
        m = ServeMetrics(failed=1, cancelled=2, timed_out=3, preempted=4,
                         retried=5, kernel_fallbacks=6)
        d = m.to_dict()
        for key, val in (("failed", 1), ("cancelled", 2), ("timed_out", 3),
                         ("preempted", 4), ("retried", 5),
                         ("kernel_fallbacks", 6)):
            assert d[key] == val

    def test_deadline_ms_validation(self):
        with pytest.raises(ValueError, match="deadline_ms"):
            SamplingParams(deadline_ms=0.0)
        with pytest.raises(ValueError, match="deadline_ms"):
            SamplingParams(deadline_ms=-5.0)
        assert SamplingParams(deadline_ms=10.0).deadline_ms == 10.0


@pytest.mark.slow
class TestShardedChaos:
    def test_sharded_quarantine_parity(self):
        """NaN quarantine on the tensor-parallel engine (8 forced host
        devices): poisoned slot fails, co-scheduled slots bit-identical
        to the fault-free sharded run, guarded tick shardings intact."""
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        code = textwrap.dedent("""
            import json, jax, numpy as np
            from repro import configs
            from repro.launch.mesh import make_serving_mesh
            from repro.models import api
            from repro.serving import (Engine, EngineConfig, Request,
                                       ServeFaultInjector, FINISH_NUMERIC)

            cfg = configs.get_smoke("tinyllama-1.1b", dtype="float32",
                                    param_dtype="float32")
            params = api.init(cfg, jax.random.key(0))
            rng = np.random.RandomState(0)
            specs = [(6, 6), (9, 8), (4, 6)]
            def reqs():
                r = np.random.RandomState(1)
                return [Request(rid=i,
                                prompt=r.randint(0, cfg.vocab, (s,)),
                                max_new_tokens=g)
                        for i, (s, g) in enumerate(specs)]
            base = Engine(cfg, params, EngineConfig(n_slots=3),
                          mesh=make_serving_mesh("2x4"))
            outs0, _ = base.run(reqs())
            inj = ServeFaultInjector(poison={2: (1,)})
            eng = Engine(cfg, params,
                         EngineConfig(n_slots=3, injector=inj),
                         mesh=make_serving_mesh("2x4"))
            outs, m = eng.run(reqs())
            print(json.dumps({
                "reason1": outs[1].finish_reason,
                "numeric": FINISH_NUMERIC,
                "match0": bool(np.array_equal(outs0[0].tokens,
                                              outs[0].tokens)),
                "match2": bool(np.array_equal(outs0[2].tokens,
                                              outs[2].tokens)),
                "failed": m.failed,
                "free_slots": m.pool["free_slots"],
            }))
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-4000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["reason1"] == res["numeric"]
        assert res["match0"] and res["match2"]
        assert res["failed"] == 1
        assert res["free_slots"] == 3
