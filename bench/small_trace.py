#!/usr/bin/env python3
"""Records a small profiler trace of the engine on the chip, for the CPU
tests of the trace readers (``bench/tests/data/chip_trace``).

    python bench/small_trace.py OUT_DIR

A smoke-width model (2 layers, d 256, one KV head of 128, Pallas kernels)
served through the paged engine with a ``Tracer`` attached: one request
due at once, a second after the first has finished (so the loop idles
between them), prefills of 128 tokens and a few decode ticks, traced
inside a ``bench_window`` annotation after a warm-up.  Writes
``OUT_DIR/phases.xplane.pb`` and ``OUT_DIR/phases.tracer.json`` (the
Tracer's events of the window, engine clock).  Exits non-zero without a
TPU.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> None:
    out = Path(sys.argv[1])
    import jax
    import numpy as np

    from repro import configs
    from repro.models import api
    from repro.obs import Tracer
    from repro.serving import Engine, EngineConfig, Request

    from bench import harness, trace

    harness.devices(1)
    cfg = configs.get_smoke("tinyllama-1.1b", d_model=256, n_heads=2,
                            n_kv_heads=1, d_ff=512, vocab=1024, max_seq=512,
                            kernel_impl="pallas")
    params = api.init(cfg, jax.random.key(0))
    tr = Tracer()
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, pool="paged", page_size=16, n_pages=64, s_max=256,
        tracer=tr))
    eng.warmup([128])
    tr.clear()
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, (128,)),
                    max_new_tokens=6, arrival_time=t)
            for i, t in enumerate((0.0, 0.15))]
    tmp = out / "profile"
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        eng.run(reqs)
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    trace.find_xplane(tmp).replace(out / "phases.xplane.pb")
    (out / "phases.tracer.json").write_text(json.dumps(
        [list(e[:2]) + [list(e[2])] + list(e[3:]) for e in tr.events]))
    print(f"wrote {out / 'phases.xplane.pb'}")


if __name__ == "__main__":
    main()
