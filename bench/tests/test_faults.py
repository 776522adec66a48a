"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole harness (set-up, window, check) at smoke
widths on the CPU, skipping only the look for a chip, with one fault of
``bench/faults.py`` planted in the program's serving path.
"""

import dataclasses
import json
import time

import pytest

from bench import faults, harness
from conftest import ROOT


def _run(cell, seed=3):
    return harness.run_cell(cell, seed, 1.0, False,
                            t_process=time.perf_counter(),
                            require_tpu=False, log=lambda *a: None)


def test_sound_run_is_correct(tiny_cell):
    assert _run(tiny_cell())["correct"] is True


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(tiny_cell, fault):
    with faults.FAULTS[fault]():
        r = _run(tiny_cell())
    assert r["correct"] is False
    c = r["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3, 2 ** 31 + 9])
def test_half_batch_fails_at_the_chat_cells_check_size(tiny_cell, seed):
    """The chat cell's own sample size (``check`` of
    bench/traffic/chat.json) catches half the slots answered with the
    other half's tokens, on every seed."""
    chat = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    cell = tiny_cell()
    cell = dataclasses.replace(cell, mix=dict(cell.mix, check=chat["check"]))
    with faults.half_batch():
        r = _run(cell, seed)
    assert r["correct"] is False
