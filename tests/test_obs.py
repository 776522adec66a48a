"""Observability subsystem: tracer, metrics, export, engine integration.

The contract under test is the obs-smoke CI gate: every request served
through a traced engine — including every chaos fault class — leaves a
complete lifecycle span chain whose finish instant matches the engine's
reported finish reason; the exported Chrome trace is structurally valid;
every decode pass records its host phases in order; and tracing costs a
bounded number of host-side records per tick (its time is measured on the
chip, by the benchmark).
"""

import json
import re
import subprocess
import sys
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops
from repro.kernels.tuning import dispatch
from repro.models import api
from repro.obs import (ENGINE_TRACK, Histogram, MetricsRegistry, Tracer,
                       load_events, percentile, request_chains, summarize,
                       to_chrome_trace, validate_chains,
                       validate_chrome_trace, write_chrome_trace,
                       write_jsonl)
from repro.serving import (Engine, EngineConfig, FINISH_CANCELLED,
                           FINISH_DEADLINE, FINISH_LENGTH, FINISH_NUMERIC,
                           FINISH_REJECTED, Request, SamplingParams,
                           ServeFaultInjector, ServeMetrics,
                           generate_sequential)

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


def _traced_run(cfg, params, specs, seed=0, **ecfg_kw):
    tr = Tracer()
    eng = Engine(cfg, params,
                 EngineConfig(tracer=tr, **ecfg_kw))
    outs, m = eng.run(_requests(cfg, np.random.RandomState(seed), specs))
    return tr, outs, m


# -- metrics primitives ------------------------------------------------------


class TestPercentile:
    def test_matches_numpy_linear(self):
        rng = np.random.RandomState(0)
        vals = list(rng.randn(137))
        for q in (0.0, 12.5, 50.0, 95.0, 99.0, 100.0):
            assert percentile(vals, q) == pytest.approx(
                float(np.percentile(vals, q)), rel=1e-12, abs=1e-12)

    def test_edges(self):
        assert percentile([], 50.0) == 0.0
        assert percentile([3.0], 99.0) == 3.0
        with pytest.raises(ValueError):
            percentile([1.0], 101.0)

    def test_summarize_keys(self):
        s = summarize([1.0, 2.0, 3.0])
        assert s["count"] == 3 and s["mean"] == pytest.approx(2.0)
        assert set(s) == {"count", "mean", "min", "max",
                          "p50", "p95", "p99"}
        z = summarize([])
        assert z["count"] == 0 and z["p99"] == 0.0


class TestInstruments:
    def test_histogram_exact_below_capacity(self):
        h = Histogram(capacity=64)
        for v in range(10):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 10 and s["min"] == 0.0 and s["max"] == 9.0
        assert s["p50"] == pytest.approx(np.percentile(np.arange(10.0), 50))

    def test_histogram_reservoir_deterministic_and_exact_moments(self):
        def run():
            h = Histogram(capacity=32)
            for v in range(1000):
                h.observe(float(v))
            return h

        a, b = run(), run()
        assert a.summary() == b.summary()  # same LCG stream, same result
        s = a.summary()
        # moments are exact even though percentiles are sampled
        assert s["count"] == 1000
        assert s["mean"] == pytest.approx(499.5)
        assert s["min"] == 0.0 and s["max"] == 999.0
        assert len(a._values) == 32

    def test_registry_get_or_create_and_dict(self):
        reg = MetricsRegistry()
        assert reg.histogram("h") is reg.histogram("h")
        reg.histogram("h").observe(1.5)
        d = reg.to_dict()
        assert d["histograms"]["h"]["count"] == 1
        json.dumps(d)  # snapshot must be JSON-clean


# -- tracer ------------------------------------------------------------------


class TestTracer:
    def test_span_begin_end_pairing(self):
        tr = Tracer(clock=lambda: 1.0)
        tr.begin("queued", ("req", 0), note="a")
        assert tr.open_spans()
        dur = tr.end("queued", ("req", 0), t=3.0)
        assert dur == pytest.approx(2.0)
        assert not tr.open_spans()
        ev = list(tr.events)[0]
        assert ev[0] == "span" and ev[1] == "queued"
        assert ev[5]["note"] == "a"  # begin args survive into the span

    def test_end_without_begin_is_noop(self):
        tr = Tracer()
        assert tr.end("decode", ("req", 1)) is None
        assert len(tr) == 0

    def test_ring_buffer_drops_oldest_and_counts(self):
        tr = Tracer(capacity=4)
        for i in range(10):
            tr.instant(f"e{i}", ENGINE_TRACK, t=float(i))
        assert len(tr) == 4
        assert tr.dropped == 6
        assert [e[1] for e in tr.events] == ["e6", "e7", "e8", "e9"]

    def test_bound_clock_moves_timeline(self):
        now = [5.0]
        tr = Tracer().bind_clock(lambda: now[0])
        tr.instant("a")
        now[0] = 9.0
        tr.instant("b")
        ts = [e[3] for e in tr.events]
        assert ts == [5.0, 9.0]

    def test_clear_resets_everything(self):
        tr = Tracer(capacity=2)
        tr.begin("s", ("req", 0))
        for i in range(5):
            tr.instant(f"e{i}")
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0 and not tr.open_spans()


# -- export ------------------------------------------------------------------


def _small_tracer():
    tr = Tracer()
    tr.instant("submitted", ("req", 3), t=0.0)
    tr.span("prefill", ("req", 3), 0.01, 0.02, slot=1)
    tr.instant("finish", ("req", 3), t=0.05, reason="length", n_tokens=4)
    tr.counter("active_slots", 2, t=0.03)
    tr.span("tick", ENGINE_TRACK, 0.02, 0.03)
    return tr


class TestExport:
    def test_chrome_trace_structure(self):
        obj = to_chrome_trace(_small_tracer(), {"k": 1})
        assert validate_chrome_trace(obj) == []
        phs = {e["ph"] for e in obj["traceEvents"]}
        assert {"M", "X", "i", "C"} <= phs
        assert obj["otherData"]["k"] == 1
        assert obj["otherData"]["dropped_events"] == 0
        # spans land in microseconds
        x = [e for e in obj["traceEvents"]
             if e["ph"] == "X" and e["name"] == "prefill"][0]
        assert x["ts"] == pytest.approx(0.01 * 1e6)
        assert x["dur"] == pytest.approx(0.01 * 1e6)

    def test_validate_catches_structural_damage(self):
        obj = to_chrome_trace(_small_tracer())
        obj["traceEvents"].append({"ph": "X", "name": "bad", "pid": 1,
                                   "tid": 0, "ts": 0.0, "dur": -5.0})
        assert any("bad dur" in p for p in validate_chrome_trace(obj))
        assert validate_chrome_trace({"traceEvents": []})
        assert validate_chrome_trace([1, 2])

    @pytest.mark.parametrize("fmt", ["jsonl", "json"])
    def test_file_round_trip(self, fmt, tmp_path):
        tr = _small_tracer()
        path = str(tmp_path / f"t.{fmt}")
        writer = write_jsonl if fmt == "jsonl" else write_chrome_trace
        writer(path, tr, metadata={"note": "x"})
        events, meta = load_events(path)
        assert meta["note"] == "x" and meta["dropped_events"] == 0
        assert [e[:3] for e in events] == [e[:3] for e in tr.events]
        # times survive the round trip (chrome goes through microseconds)
        assert events[0][3] == pytest.approx(0.0, abs=1e-9)
        assert events[1][4] == pytest.approx(0.01, rel=1e-6)

    def test_request_chains_and_validation(self):
        tr = _small_tracer()
        chains = request_chains(tr)
        assert chains[3]["finish"] == "length"
        assert chains[3]["n_tokens"] == 4
        assert chains[3]["instants"][-1] == "finish"
        # rid 3 finished "length" but has no first_token instant
        probs = validate_chains(tr)
        assert any("first_token" in p for p in probs)

    def test_validate_chains_flags_leaks_and_mismatches(self):
        tr = Tracer()
        tr.begin("decode", ("req", 0))
        probs = validate_chains(tr, expect={0: "length", 7: "stop"})
        assert any("never closed" in p for p in probs)
        assert any("rid 7" in p for p in probs)


# -- ServeMetrics round trip -------------------------------------------------


class TestServeMetricsDict:
    def test_zero_tick_to_dict(self):
        m = ServeMetrics()
        d = m.to_dict()
        assert d["ttft"]["count"] == 0 and d["itl"]["count"] == 0
        assert d["decode_tok_per_s"] == 0.0
        assert d["occupancy"] == 0.0
        json.dumps(d)

    def test_round_trip_identity(self):
        m = ServeMetrics()
        m.n_requests = 3
        m.n_slots = 2
        m.decode_ticks = 7
        m.decode_tokens = 14
        m.decode_time_s = 0.5
        m.ttft_s = {0: 0.1, 1: 0.2}
        m.ttft_samples = [0.1, 0.2]
        m.itl_samples = [0.01, 0.02, 0.03]
        m.kernel_fallbacks_by_kernel = {"gs_recip": 2}
        m.dispatch = {"resolves": {"gs_softmax": 4}}
        d = json.loads(json.dumps(m.to_dict()))
        m2 = ServeMetrics.from_dict(d)
        assert m2.to_dict() == m.to_dict()
        assert m2.ttft_s == {0: 0.1, 1: 0.2}  # keys back to int
        assert m2.ttft_summary["count"] == 2

    def test_from_dict_rejects_unknown(self):
        with pytest.raises(ValueError, match="unknown"):
            ServeMetrics.from_dict({"not_a_field": 1})

    def test_run_populates_latency_samples(self, model):
        cfg, params = model
        eng = Engine(cfg, params, EngineConfig(n_slots=2))
        reqs = _requests(cfg, np.random.RandomState(0),
                         [(6, 5, 0.0), (9, 4, 0.0), (4, 3, 0.0)])
        outs, m = eng.run(reqs)
        assert len(m.ttft_samples) == m.first_tokens == len(reqs)
        assert len(m.itl_samples) == m.decode_tokens
        assert all(v > 0 for v in m.itl_samples)
        assert m.ttft_summary["p99"] >= m.ttft_summary["p50"] > 0
        d = m.to_dict()
        assert d["itl"]["count"] == m.decode_tokens


# -- engine integration ------------------------------------------------------


class TestEngineTracing:
    def test_clean_run_chains_close(self, model):
        cfg, params = model
        tr, outs, m = _traced_run(
            cfg, params, [(6, 5, 0.0), (9, 8, 0.0), (4, 3, 0.02),
                          (7, 6, 0.03)], n_slots=2)
        expect = {r: outs[r].finish_reason for r in outs.keys()}
        assert validate_chains(tr, expect) == []
        assert validate_chrome_trace(
            to_chrome_trace(tr, {"metrics": m.to_dict()})) == []
        chains = request_chains(tr)
        assert len(chains) == 4
        for c in chains.values():
            assert c["finish"] == FINISH_LENGTH
            assert "queued" in c["spans"] and "prefill" in c["spans"]
        # engine-track ticks recorded once per decode tick
        ticks = [e for e in tr.events
                 if e[0] == "span" and e[1] == "tick"]
        assert len(ticks) == m.decode_ticks

    def test_tracing_changes_no_tokens(self, model):
        cfg, params = model
        specs = [(6, 5, 0.0), (9, 8, 0.0), (4, 3, 0.0)]
        eng0 = Engine(cfg, params, EngineConfig(n_slots=2))
        outs0, _ = eng0.run(_requests(cfg, np.random.RandomState(3), specs))
        tr, outs, _ = _traced_run(cfg, params, specs, seed=3, n_slots=2)
        for rid in outs0.keys():
            np.testing.assert_array_equal(outs0[rid].tokens,
                                          outs[rid].tokens)

    def test_prefix_hit_marked_in_prefill_span(self, model):
        cfg, params = model
        tr = Tracer()
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=2, pool="paged", page_size=4,
                                  n_pages=24, tracer=tr))
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, cfg.vocab, (6,))
        reqs = [Request(rid=i, prompt=prompt, max_new_tokens=4)
                for i in range(3)]
        outs, m = eng.run(reqs)
        assert m.prefill_skips == 2
        hits = [e for e in tr.events
                if e[0] == "span" and e[1] == "prefill"
                and (e[5] or {}).get("hit")]
        assert len(hits) == 2
        assert validate_chains(
            tr, {r.rid: outs[r.rid].finish_reason for r in reqs}) == []

    def test_pool_track_events(self, model):
        """COW + prefix eviction instants land on the pool track."""
        cfg, params = model
        tr = Tracer()
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=2, s_max=12, pool="paged",
                                  page_size=4, n_pages=7, tracer=tr))
        rng = np.random.RandomState(0)
        # distinct prompts through a tight arena force prefix eviction
        reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (6,)),
                        max_new_tokens=4) for i in range(4)]
        eng.run(reqs)
        pool_evs = [e[1] for e in tr.events if e[2] == ("pool", 0)]
        assert "prefix_evict" in pool_evs


class TestChaosChains:
    """Every fault class leaves a complete chain with the right reason."""

    def test_poison_quarantine_chain(self, model):
        cfg, params = model
        tr = Tracer()
        inj = ServeFaultInjector(poison={2: (1,)})
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=3, injector=inj, tracer=tr))
        reqs = _requests(cfg, np.random.RandomState(0),
                         [(6, 6, 0.0), (9, 8, 0.0), (4, 6, 0.0)])
        outs, m = eng.run(reqs)
        assert outs[1].finish_reason == FINISH_NUMERIC
        expect = {r.rid: outs[r.rid].finish_reason for r in reqs}
        assert validate_chains(tr, expect) == []
        quar = [e for e in tr.events
                if e[0] == "inst" and e[1] == "quarantine"]
        assert len(quar) == 1 and quar[0][2] == ("req", 1)

    def test_cancel_chain(self, model):
        cfg, params = model
        tr = Tracer()
        inj = ServeFaultInjector(cancels={2: (1,)})
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=3, injector=inj, tracer=tr))
        reqs = _requests(cfg, np.random.RandomState(0),
                         [(6, 6, 0.0), (9, 8, 0.0), (4, 6, 0.0)])
        outs, _ = eng.run(reqs)
        assert outs[1].finish_reason == FINISH_CANCELLED
        assert validate_chains(
            tr, {r.rid: outs[r.rid].finish_reason for r in reqs}) == []

    def test_skew_deadline_chain_and_trace_clock(self, model):
        """Clock skew expires deadlines AND moves the trace timeline:
        the tracer rides the same skewed engine clock."""
        cfg, params = model
        tr = Tracer()
        inj = ServeFaultInjector(skew={3: 100.0})
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=2, injector=inj, tracer=tr))
        # compile first: the 5 s deadline is for serving, and compiling
        # on a loaded machine can outlast it before tick 3's skew lands
        # (the warm-up's single tick leaves tick 3's event unconsumed)
        eng.warmup([6, 5])
        tr.clear()
        reqs = _requests(cfg, np.random.RandomState(2),
                         [(6, 8, 0.0), (5, 8, 0.0)], deadline_ms=5000.0)
        outs, _ = eng.run(reqs)
        assert all(outs[r.rid].finish_reason == FINISH_DEADLINE
                   for r in reqs)
        assert validate_chains(
            tr, {r.rid: FINISH_DEADLINE for r in reqs}) == []
        # post-skew events carry the jumped clock
        finish_ts = [e[3] for e in tr.events
                     if e[0] == "inst" and e[1] == "finish"]
        assert max(finish_ts) >= 100.0

    def test_rejected_chain(self, model):
        cfg, params = model
        tr = Tracer()
        eng = Engine(cfg, params,
                     EngineConfig(n_slots=1, max_queue=1, max_retries=0,
                                  tracer=tr))
        reqs = _requests(cfg, np.random.RandomState(10),
                         [(6, 4, 0.0), (5, 4, 0.0), (4, 4, 0.0)])
        outs, m = eng.run(reqs)
        assert m.failed == 2
        expect = {r.rid: outs[r.rid].finish_reason for r in reqs}
        assert sorted(expect.values()).count(FINISH_REJECTED) == 2
        assert validate_chains(tr, expect) == []


# engine-track records of one decode pass: the admit, page_append,
# tick_prepare, tick_dispatch, tick_wait and emit phases, the tick span
# and the active_slots / ready_queue counters
ENGINE_EVENTS_PER_TICK = 9
# one request's records: submitted, queued, prefill, first_token, decode
# and finish on its track, resident on its slot's, and its prefill phase
# on the engine's
EVENTS_PER_REQUEST = 8
TICK_PHASES = ("engine.tick_prepare", "engine.tick_dispatch",
               "engine.tick_wait", "engine.emit")


def _host_values(ev):
    """Every value an event holds, its args' values included."""
    vals = list(ev[2]) + list(ev[3:5])
    args = ev[5] if ev[0] == "span" else (ev[4] if ev[0] == "inst"
                                          else None)
    if isinstance(args, dict):
        vals += list(args.values())
    return vals


class TestTracingOverhead:
    def test_tick_cost_within_budget(self, model):
        """Tracing's cost, counted: a fixed number of records per decode
        tick and per request, none holding a device array (so no extra
        device->host transfer), and the same tokens as an untraced run.
        Its time is measured on the chip (PERF.md), not here."""
        cfg, params = model
        specs = [(8, 16, 0.0), (6, 16, 0.0), (7, 16, 0.0), (5, 16, 0.0)]
        # pages for every request's whole life and preemption off (it
        # fires whenever every slot is busy, PERF.md open question 2):
        # no appends, no replays
        ecfg = dict(n_slots=2, pool="paged", page_size=4, n_pages=64,
                    page_reserve="worst", preempt_after_ticks=10 ** 9)
        tr, outs, m = _traced_run(cfg, params, specs, seed=4, **ecfg)
        eng = Engine(cfg, params, EngineConfig(**ecfg))
        outs0, _ = eng.run(_requests(cfg, np.random.RandomState(4), specs))
        for rid in outs0.keys():
            np.testing.assert_array_equal(outs0[rid].tokens,
                                          outs[rid].tokens)
        events = list(tr.events)
        # + run, run_start, run_end
        assert len(events) <= (ENGINE_EVENTS_PER_TICK * m.decode_ticks
                               + EVENTS_PER_REQUEST * len(specs) + 3)
        for ev in events:
            for v in _host_values(ev):
                assert not isinstance(v, (jax.Array, np.ndarray)), ev

    def test_untraced_run_records_nothing(self, model, monkeypatch):
        from repro.obs import trace as trace_mod

        def refuse(*a, **k):
            raise AssertionError("an untraced run recorded an event")

        monkeypatch.setattr(trace_mod.Tracer, "_push", refuse)
        monkeypatch.setattr(trace_mod, "TraceAnnotation", refuse)
        cfg, params = model
        eng = Engine(cfg, params, EngineConfig(n_slots=2, pool="paged",
                                               page_size=4, n_pages=24))
        outs, m = eng.run(_requests(cfg, np.random.RandomState(5),
                                    [(6, 4, 0.0), (5, 3, 0.01)]))
        assert m.decode_ticks > 0


# -- host phases ------------------------------------------------------------


class TestEnginePhases:
    def test_every_decode_pass_records_its_phases_in_order(self, model):
        cfg, params = model
        tr, outs, m = _traced_run(
            cfg, params, [(6, 5, 0.0), (9, 8, 0.0), (4, 3, 0.02)],
            n_slots=2, pool="paged", page_size=4, n_pages=24)
        assert not tr.open_spans()
        phases = [e for e in tr.events
                  if e[0] == "span" and e[1].startswith("engine.")]
        by_tick = {}
        for e in phases:
            if e[1] in TICK_PHASES:
                by_tick.setdefault(e[5]["tick"], []).append(e)
        assert sorted(by_tick) == list(range(m.decode_ticks))
        for tick, evs in by_tick.items():
            assert tuple(e[1] for e in evs) == TICK_PHASES, tick
            for a, b in zip(evs, evs[1:]):
                assert a[4] >= 0 and a[3] + a[4] <= b[3], (a, b)
        # every pass's other phases carry its tick too; one run phase
        # holds them all, from engine-clock ~0
        run = [e for e in phases if e[1] == "engine.run"]
        assert len(run) == 1 and run[0][3] < 0.5
        end = run[0][3] + run[0][4]
        for e in phases:
            if e[1] != "engine.run":
                assert 0 <= e[5]["tick"] <= m.decode_ticks, e
                assert run[0][3] <= e[3] and e[3] + e[4] <= end, e
        prefills = [e for e in phases if e[1] == "engine.prefill"]
        assert sorted(e[5]["rid"] for e in prefills) == [0, 1, 2]
        assert {e[5]["rid"]: e[5]["prompt_len"] for e in prefills} == \
            {0: 6, 1: 9, 2: 4}
        # the request span keeps its name and args beside the phase
        assert len([e for e in tr.events if e[0] == "span"
                    and e[1] == "prefill"]) == 3

    def test_phase_lands_in_the_profiler_trace(self, tmp_path):
        tr = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.phase("admit", tick=7):
                with tr.phase("prefill", tick=7, rid=3):
                    pass
        finally:
            jax.profiler.stop_trace()
        xplane = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
        pd = jax.profiler.ProfileData.from_file(str(xplane))
        host = {}
        for plane in pd.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        host[e.name] = (e.start_ns, e.duration_ns)
        assert {"engine.admit", "engine.prefill"} <= set(host)
        a, p = host["engine.admit"], host["engine.prefill"]
        assert a[0] <= p[0] and p[0] + p[1] <= a[0] + a[1]
        spans = {e[1]: e for e in tr.events}
        assert spans["engine.prefill"][5] == {"tick": 7, "rid": 3}
        assert spans["engine.admit"][2] == ENGINE_TRACK


# scopes the decode tick's program is named by (PERF.md section 3)
TICK_SCOPES = ("embed", "rmsnorm", "attn_proj", "weights_cast", "rope",
               "kv_write", "gather_pages", "decode_attention", "kv_mask",
               "mlp", "layer_scan", "lm_head", "sampler")


@pytest.fixture(scope="module")
def paged_tick_text(model):
    _, params = model
    # bfloat16 compute over the fixture's float32 weights: the tick
    # casts them (lowered only, never run)
    cfg = configs.get_smoke("tinyllama-1.1b", dtype="bfloat16",
                            param_dtype="float32")
    eng = Engine(cfg, params, EngineConfig(n_slots=2, pool="paged",
                                           page_size=4, n_pages=24))
    n = 2
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    from repro.serving.cache import make_paged_cache

    cache = make_paged_cache(cfg, n, eng._n_pages, eng.ecfg.page_size,
                             np.dtype(cfg.dtype))
    tick = eng._tick_fn(False, 0, eng.ecfg.numeric_guard)
    return tick.lower(params, cache, i32(n, eng._pages_per_slot), i32(n),
                      i32(n, 1), np.zeros(n, np.float32), i32(n), i32(n),
                      jax.random.key(0)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", TICK_SCOPES)
def test_paged_tick_holds_named_scope(paged_tick_text, scope):
    # op locations name their scope path, e.g. "attn_proj/weights_cast/
    # convert_element_type", relative to the function that holds them
    assert re.search(rf'(loc\("|/){scope}/', paged_tick_text), scope


# -- dispatch counters -------------------------------------------------------


class TestDispatchCounters:
    def test_resolve_counts(self):
        dispatch.reset_dispatch_stats()
        start = dispatch.dispatch_snapshot()
        x = np.linspace(0.5, 2.0, 8).astype(np.float32)
        ops.gs_recip(x)
        delta = dispatch.dispatch_delta(start)
        assert delta["resolves"].get("gs_recip", 0) >= 1

    def test_tune_hit_miss_counters(self):
        dispatch.reset_dispatch_stats()
        dispatch.enable_tuning(True)
        try:
            start = dispatch.dispatch_snapshot()
            x = np.linspace(0.5, 2.0, 16).astype(np.float32)
            ops.gs_recip(x)
            delta = dispatch.dispatch_delta(start)
        finally:
            dispatch.enable_tuning(None)
        hits = delta["tune_hits"].get("gs_recip", 0)
        misses = delta["tune_misses"].get("gs_recip", 0)
        assert hits + misses >= 1  # tuning consulted either way

    def test_delta_drops_zero_entries(self):
        dispatch.reset_dispatch_stats()
        start = dispatch.dispatch_snapshot()
        assert dispatch.dispatch_delta(start, start) == {
            "resolves": {}, "tune_hits": {}, "tune_misses": {},
            "fallbacks": {}}

    def test_fallback_attribution_reaches_metrics(self, model,
                                                  monkeypatch):
        """A kernel fault during a pallas-served run shows up per-kernel
        in ServeMetrics.kernel_fallbacks_by_kernel."""
        import warnings

        cfg, params = model
        dispatch.reset_fallback_stats()

        def boom(*a, **k):
            raise RuntimeError("injected kernel fault")

        monkeypatch.setattr(ops, "_gs_recip", boom)
        monkeypatch.setattr(dispatch, "_fallback_override", True)  # opt in
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            np.asarray(ops.gs_recip(np.ones(4, np.float32)))
            eng = Engine(cfg, params, EngineConfig(n_slots=1))
            outs, m = eng.run(_requests(cfg, np.random.RandomState(0),
                                        [(5, 3, 0.0)]))
        # the engine run diffs process-wide stats: the pre-run downgrade
        # must NOT be attributed to it, and its own count is >= 0
        assert m.kernel_fallbacks == sum(
            m.kernel_fallbacks_by_kernel.values())
        assert "gs_recip" not in m.kernel_fallbacks_by_kernel or \
            m.kernel_fallbacks_by_kernel["gs_recip"] >= 1
        dispatch.reset_fallback_stats()


# -- generate_sequential satellite -------------------------------------------


class TestSequentialTTFT:
    def test_ttft_is_measured_not_zero(self, model):
        cfg, params = model
        out = generate_sequential(
            cfg, params,
            Request(rid=0, prompt=np.arange(8), max_new_tokens=4))
        assert 0.0 < out.ttft_s <= out.finish_s


# -- CLI ---------------------------------------------------------------------


class TestObsView:
    @pytest.mark.parametrize("ext", ["json", "jsonl"])
    def test_serve_trace_out_then_obsview(self, ext, tmp_path):
        path = str(tmp_path / f"trace.{ext}")
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--smoke",
             "--batch", "2", "--prompt-len", "8", "--gen", "4",
             "--trace-out", path],
            capture_output=True, text=True, env=env, cwd=REPO)
        assert r.returncode == 0, r.stderr
        assert "trace:" in r.stdout
        events, meta = load_events(path)
        assert events and meta["metrics"]["n_requests"] == 2
        v = subprocess.run(
            [sys.executable, "-m", "repro.launch.obsview", path],
            capture_output=True, text=True, env=env, cwd=REPO)
        assert v.returncode == 0, v.stderr
        assert "2 requests" in v.stdout
        assert "TTFT" in v.stdout

    def test_summarize_trace_lines(self, model):
        from repro.launch.obsview import summarize_trace

        cfg, params = model
        tr, outs, m = _traced_run(cfg, params,
                                  [(6, 5, 0.0), (4, 3, 0.0)], n_slots=2)
        lines = summarize_trace(list(tr.events),
                                {"metrics": m.to_dict()})
        text = "\n".join(lines)
        assert "2 requests" in text
        assert "length 2" in text  # finish reasons
        assert "tick" in text
        # one line per host phase, with its p50 and p95
        for phase in ("run", "admit", "prefill", "tick_prepare",
                      "tick_dispatch", "tick_wait", "emit"):
            line = [x for x in lines if x.strip().startswith(
                f"engine.{phase}:")]
            assert len(line) == 1, phase
            assert "p50" in line[0] and "p95" in line[0]
