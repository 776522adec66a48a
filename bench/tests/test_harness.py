"""What the benchmark promises its caller, checked on the CPU."""

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import harness, weights
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


# weights.make of the untied tiny configuration (key 7, std 0.02) on the
# commit before the file stated its head: path, shape and bytes of every
# leaf, in order
UNTIED_DIGEST = ("d935e812c909d83a1d84e68493d8ad63"
                 "df53bc8d65b3fa4c7805d4c36d6c56a1")


def _digest(params):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _weights_of(cell, key=7):
    cfg = harness.build_cfg(cell)
    return cfg, weights.make(harness.arch_sizes(cfg, cell.config),
                             jax.random.key(key), std=0.02)


def test_untied_weights_are_unchanged(tiny_cell):
    _, params = _weights_of(tiny_cell())
    assert "lm_head" in params
    assert _digest(params) == UNTIED_DIGEST


def test_tied_weights_have_no_head_and_fit_the_program(tiny_cell):
    cell = tiny_cell("tiny-tied.chat")
    cfg, params = _weights_of(cell)
    assert cfg.tie_embeddings and "lm_head" not in params
    harness._check_layout(cfg, params)
    _, untied = _weights_of(tiny_cell())
    untied_cfg = harness.build_cfg(tiny_cell())
    with pytest.raises(harness.BenchError, match="layout"):
        harness._check_layout(dataclasses.replace(
            untied_cfg, tie_embeddings=True), untied)


@pytest.mark.parametrize("key, value", [
    ("embedding_multiplier", 12.0), ("attention_multiplier", 0.0078125),
    ("residual_multiplier", 0.22), ("logits_scaling", 16.0)])
def test_a_key_the_program_has_no_field_for_stops_set_up(tiny_cell, key,
                                                        value):
    """The reference would compute a scalar the program leaves out: the
    run stops in set-up, before any weight is made, naming the key."""
    cell = tiny_cell()
    cell.config = dict(cell.config, **{key: value})
    with pytest.raises(harness.BenchError, match=key):
        harness.set_up(cell, 1, [], require_tpu=False)
