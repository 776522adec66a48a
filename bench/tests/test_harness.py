"""What the benchmark promises its caller, checked on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT, CELLS[0])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path, CELLS[0])
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files(name):
    cell = harness.load_cell(name)
    assert cell.chips in (1, 4)
    assert cell.end_to_end and cell.per_layer
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    reported = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in reported
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    assert cell.limits["max_logit_gap"] > 0
    cfg = harness.build_cfg(cell)
    harness._check_sizes(cell, cfg)


def test_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell("no-such-model.chat")


def test_seed_keys_fit_jax_and_differ():
    keys = {harness.seed_key(s, 0) for s in (0, 1, 2 ** 31 + 5, 2 ** 40)}
    assert len(keys) == 4 and all(0 <= k < 2 ** 31 for k in keys)
