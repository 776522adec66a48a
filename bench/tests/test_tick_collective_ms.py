"""The tick_collective_ms reader on a hand-made decoded trace."""

import pytest

from bench import harness

MS = 1_000_000  # ns


def _record(ops, ticks=2):
    """A record whose decoded trace (``attribution.for_record``'s cache)
    holds ``ops`` on chip 0 and ``ticks`` tick_dispatch phases."""
    rec = harness.Record(arch={}, chips=4, peaks={}, outs={}, serve=None,
                         window_s=1.0, setup_s=0.0, trace={"window_s": 1.0})
    host = [("engine.tick_dispatch", i * 10 * MS, MS, "main")
            for i in range(ticks)]
    rec._attribution = {"devices": {"/device:TPU:0": ops}, "host": host,
                        "lo": 0, "hi": 1000 * MS}
    return rec


def test_collectives_by_opcode_per_tick():
    read = harness.metric_reader("tick_collective_ms")
    ops = [("jit_tick/all-reduce", "jit(tick)/mlp/dot_general", 0, 2 * MS),
           ("jit_tick/all-reduce-start", "", 5 * MS, MS),
           ("jit_tick/all-reduce-done", "", 5 * MS, 2 * MS),  # overlaps
           ("jit_tick/all-gather", "jit(tick)/embed", 10 * MS, MS),
           ("jit_tick/fusion", "jit(tick)/mlp", 20 * MS, 5 * MS),
           ("jit_prefill_step/all-reduce", "", 30 * MS, 9 * MS)]
    # 2 + 2 (the async pair's union) + 1 ms over 2 ticks
    assert read(_record(ops)) == pytest.approx(2.5)


def test_a_tick_without_collectives_reads_nothing():
    read = harness.metric_reader("tick_collective_ms")
    ops = [("jit_tick/fusion", "jit(tick)/mlp", 0, 5 * MS),
           ("jit_tick/reduce", "", 6 * MS, MS)]
    assert read(_record(ops)) is None
    assert read(harness.Record(arch={}, chips=1, peaks={}, outs={},
                               serve=None, window_s=1.0,
                               setup_s=0.0)) is None
