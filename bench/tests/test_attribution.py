"""The metadata decoder and the attribution of device and idle time, on
synthetic events and on traces recorded on the chip
(bench/tests/data/chip_trace)."""

import json
import shutil
import types

import pytest

from bench import attribution as A
from bench import harness, trace
from conftest import DATA

MS = 1_000_000  # ns
SMALL = DATA / "chip_trace" / "small.xplane.pb"
PHASES = DATA / "chip_trace" / "phases.xplane.pb"
NEW_METRICS = ("tick_device_ms", "tick_host_ms", "tick_cast_ms",
               "tick_gather_ms", "prefill_device_ms_per_ktok")


def test_decoder_matches_profile_data():
    """Same ops, names and times as ``jax.profiler.ProfileData`` reads,
    plus the metadata it cannot give."""
    ours = A.read(SMALL)
    theirs = trace.load(SMALL)
    assert list(ours["devices"]) == list(theirs["devices"])
    for plane, ops in theirs["devices"].items():
        assert [(n, s, d) for n, _, s, d in ours["devices"][plane]] == ops
    win = [h for h in theirs["host"] if h[0] == trace.WINDOW]
    assert win == [h for h in ours["host"] if h[0] == trace.WINDOW]
    tf_ops = {n: t for n, t, _, _ in ours["devices"]["/device:TPU:0"]}
    assert tf_ops["jit_prefill_step/flash_attention"].endswith(
        "jit(flash_attention)/pallas_call:")
    assert "jit(tick)/" in tf_ops["jit_tick/gs_rmsnorm"]


def test_scope_is_the_innermost_named():
    assert A.scope_of("jit(tick)/layer_scan/while/body/closed_call/"
                      "decode_attention/kv_mask/select_n:") == "kv_mask"
    assert A.scope_of("jit(tick)/layer_scan/while/body/attn_proj/"
                      "weights_cast/convert_element_type:") == "weights_cast"
    assert A.scope_of("jit(tick)/layer_scan/while/body/dynamic_slice:") \
        == "layer_scan"
    assert A.scope_of("jit(tick)/jit(_where)/select_n:") is None
    assert A.op_class("jit_tick/fusion", "jit(tick)/reduce:") == A.UNSCOPED
    assert A.op_class("jit_tick/convert", "") == A.NO_METADATA + "convert"


def test_innermost_labels_nested_phases():
    ivs = [("run", 0, 100), ("admit", 10, 40), ("prefill", 20, 30),
           ("emit", 50, 60)]
    assert A.innermost(ivs) == [(0, 10, "run"), (10, 20, "admit"),
                                (20, 30, "prefill"), (30, 40, "admit"),
                                (40, 50, "run"), (50, 60, "emit"),
                                (60, 100, "run")]


def _events():
    # window [0, 100) ms; the device busy [5,25) [45,55) [70,80)
    dev = [("jit_tick/fusion", "jit(tick)/layer_scan/while/body/mlp/dot:",
            5 * MS, 20 * MS),
           ("jit_tick/convert", "", 45 * MS, 10 * MS),
           ("jit_prefill_step/fusion", "jit(prefill_step)/mlp/dot:",
            70 * MS, 10 * MS)]
    host = [(name, s * MS, d * MS, "python3") for name, s, d in (
        (trace.WINDOW, 0, 100), ("engine.run", 2, 96),
        ("engine.tick_dispatch", 4, 1), ("engine.tick_wait", 5, 21),
        ("engine.emit", 26, 10), ("engine.tick_dispatch", 44, 1),
        ("engine.tick_wait", 45, 11), ("engine.admit", 60, 30),
        ("engine.prefill", 65, 20))]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_reductions_on_synthetic_events():
    ev = _events()
    lo, hi = trace.window_of(ev["host"])
    assert (lo, hi) == (0, 100 * MS)
    t = A.time_by_class(ev, lo, hi, "jit_tick")
    assert t == pytest.approx({"mlp": 0.020,
                               A.NO_METADATA + "convert": 0.010})
    assert A.busy(A.chip0(ev), lo, hi, "jit_tick") == pytest.approx(0.030)
    prefill = A.phases(ev["host"], lo, hi, "prefill")
    assert A.busy(A.chip0(ev), lo, hi, within=prefill) == \
        pytest.approx(0.010)
    idle = A.idle_by_phase(ev, lo, hi)
    # idle [0,5) [25,45) [55,70) [80,100) = 60 ms
    assert sum(idle.values()) == pytest.approx(0.060)
    assert idle == pytest.approx({
        "(none)": 0.004, "tick_dispatch": 0.002, "tick_wait": 0.002,
        "emit": 0.010, "run": 0.022, "admit": 0.010, "prefill": 0.010})


def _record(trace_file, tracer_events, prefill_tokens, tmp_path,
            monkeypatch):
    """A Record as a traced run leaves it, its trace where the harness
    has the profiler write it."""
    d = tmp_path / "trace" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(trace_file, d / "host.xplane.pb")
    monkeypatch.setattr(A, "TRACE_DIR", tmp_path / "trace")
    s = trace.summarize(trace.load(trace_file), 1)
    return types.SimpleNamespace(
        trace=s, tracer_events=tracer_events,
        serve=types.SimpleNamespace(prefill_tokens=prefill_tokens))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_find_nothing_in_a_trace_without_phases(
        metric, tmp_path, monkeypatch):
    """The program before the host phases and scopes: every new reader
    reads nothing, and none raises."""
    rec = _record(SMALL, [], 128, tmp_path, monkeypatch)
    assert harness.metric_reader(metric)(rec) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_of_an_untraced_run_read_nothing(metric):
    rec = types.SimpleNamespace(trace=None, tracer_events=None,
                                serve=types.SimpleNamespace(
                                    prefill_tokens=128))
    assert harness.metric_reader(metric)(rec) is None


def _tracer_events():
    """The Tracer's events of the window recorded beside phases.xplane.pb
    (``bench/small_trace.py``), tracks back to tuples."""
    raw = json.loads((DATA / "chip_trace" / "phases.tracer.json").read_text())
    return [tuple(e[:2]) + (tuple(e[2]),) + tuple(e[3:]) for e in raw]


def test_trace_with_phases_recorded_on_the_chip():
    """A smoke-width engine run with a Tracer attached, traced on a TPU
    v5e (``bench/small_trace.py``): every host phase is on the profiler's
    clock, one offset from the engine clock, and covers the idle time."""
    ev = A.read(PHASES)
    lo, hi = trace.window_of(ev["host"])
    names = {n[len(A.PHASE):] for n, _, _, _ in ev["host"]
             if n.startswith(A.PHASE)}
    assert names == {"run", "admit", "prefill", "page_append", "idle",
                     "tick_prepare", "tick_dispatch", "tick_wait", "emit"}
    # each phase on both clocks: profiler start - engine start is one
    # offset (to the width of the clock reads, well under a millisecond)
    ours = sorted((e[1], e[3]) for e in _tracer_events()
                  if e[0] == "span" and e[1].startswith(A.PHASE))
    theirs = sorted((n, s) for n, s, _, _ in ev["host"]
                    if n.startswith(A.PHASE))
    assert [n for n, _ in ours] == [n for n, _ in theirs]
    by_name = {}
    for (n, t), (_, s) in zip(ours, theirs):
        by_name.setdefault(n, []).append(s - t * 1e9)
    offsets = [o for v in by_name.values() for o in v]
    assert max(offsets) - min(offsets) < 0.5 * MS
    idle = A.idle_by_phase(ev, lo, hi)
    inside = sum(v for k, v in idle.items() if k != "(none)")
    assert inside >= 0.9 * sum(idle.values())
    t = A.time_by_class(ev, lo, hi, "jit_tick")
    for scope in ("weights_cast", "gather_pages", "decode_attention",
                  "kv_write", "attn_proj", "mlp", "rmsnorm", "lm_head",
                  "sampler", "layer_scan"):
        assert t.get(scope, 0) > 0, scope


@pytest.fixture
def phases_record(tmp_path, monkeypatch):
    return _record(PHASES, _tracer_events(), 256, tmp_path, monkeypatch)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_read_the_trace_with_phases(metric, phases_record):
    v = harness.metric_reader(metric)(phases_record)
    assert v is not None and v > 0


def test_tick_parts_within_the_tick(phases_record):
    read = {m: harness.metric_reader(m)(phases_record) for m in NEW_METRICS}
    assert read["tick_cast_ms"] + read["tick_gather_ms"] \
        <= read["tick_device_ms"]
    # a tick's device work lies between its dispatch and the end of the
    # wait for its tokens
    ev = A.read(PHASES)
    lo, hi = trace.window_of(ev["host"])
    held = [e - s for n, s, e in A.phases(ev["host"], lo, hi)
            if n in ("tick_dispatch", "tick_wait")]
    assert len(held) == 20
    assert read["tick_device_ms"] <= sum(held) / 10 / 1e6


def test_a_stale_trace_is_not_read(phases_record):
    """The trace found must be the run's own: a window of another length
    reads nothing."""
    phases_record.trace = dict(phases_record.trace,
                               window_s=phases_record.trace["window_s"] + 1)
    assert harness.metric_reader("tick_device_ms")(phases_record) is None
