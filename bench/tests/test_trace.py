"""The trace reduction, on synthetic events and on a trace recorded on
the chip (bench/tests/data/chip_trace)."""

import pytest

from bench import trace

MS = 1_000_000  # ns


def _events():
    # window [10, 110) ms; chip 0 busy [0,20) [15,30) [50,60) [100,130)
    dev0 = [("fusion.1", 0, 20 * MS), ("fusion.2", 15 * MS, 15 * MS),
            ("custom-call.flash", 50 * MS, 10 * MS),
            ("all-reduce.3", 100 * MS, 30 * MS)]
    dev1 = [("fusion.1", 10 * MS, 50 * MS)]
    host = [(trace.WINDOW, 10 * MS, 100 * MS, "main"),
            ("PjitFunction(tick)", 30 * MS, 19 * MS, "main"),
            ("sleep", 60 * MS, 40 * MS, "main")]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host}


def test_merge_is_a_union():
    assert trace.merge([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) == \
        [(0, 4), (5, 10)]


def test_summary_of_one_chip():
    s = trace.summarize(_events(), n_chips=1)
    assert s["window_s"] == pytest.approx(0.1)
    # inside the window: [10,30) + [50,60) + [100,110) = 40 ms
    assert s["busy_s"] == pytest.approx(0.040)
    assert s["ops"]["fusion.1"] == pytest.approx(0.010)
    assert s["ops"]["all-reduce.3"] == pytest.approx(0.010)
    assert trace.time_matching(s, ["all-reduce"]) == pytest.approx(0.010)
    gaps = s["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.040, 0.020])
    assert gaps[0][0] == "main: sleep"
    assert gaps[1][0] == "main: PjitFunction(tick)"
    assert s["breakdown"]["device_ops"][0][0] in ("fusion.1", "fusion.2")


def test_busy_is_averaged_over_chips():
    s = trace.summarize(_events(), n_chips=2)
    assert s["busy_s_per_chip"] == pytest.approx([0.040, 0.050])
    assert s["busy_s"] == pytest.approx(0.045)


def test_window_annotation_is_required():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0] != trace.WINDOW]
    with pytest.raises(ValueError):
        trace.summarize(ev, 1)


def test_trace_recorded_on_the_chip():
    """A smoke-width engine run (prefill of 128 tokens, a few decode
    ticks) traced on a TPU v5e inside a ``bench_window`` annotation."""
    from conftest import DATA

    ev = trace.load(DATA / "chip_trace" / "small.xplane.pb")
    assert list(ev["devices"]) == ["/device:TPU:0"]
    names = {n for n, _, _ in ev["devices"]["/device:TPU:0"]}
    assert "jit_prefill_step/flash_attention" in names
    assert "jit_tick/gs_rmsnorm" in names
    s = trace.summarize(ev, 1)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert trace.time_matching(s, ["jit_prefill_step/flash_attention"]) > 0
    # a leaf op's time never exceeds the busy union it is part of
    assert max(s["ops"].values()) <= s["busy_s"]
    assert not any(k.endswith("/while") for k in s["ops"])
    gaps = s["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_op_names_drop_the_instruction_number():
    assert trace.op_name("%flash_attention.7 = bf16[1] custom-call()") == \
        "flash_attention"
    assert trace.op_name("%while.14 = (s32[]) while()") == "while"
    assert trace.op_name("fusion") == "fusion"
