"""The benchmark's own tests (CPU).  Run by path from the repo root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The repo's tier-1 run collects only ``tests/``.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tiny_cell():
    """A cell at smoke widths (bench/tests/data), through the same
    harness, engine and reference as the real cells."""
    from bench import harness

    def load(name="tiny.chat"):
        return harness.load_cell(name, bench_dir=DATA,
                                 spec_path=DATA / "BENCHMARK.json")

    return load
