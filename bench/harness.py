"""Runs one benchmark cell once; everything cell-specific is found by name.

``BENCHMARK.json`` names the cells.  A cell ``<config>.<mix>`` reads

* ``bench/configs/<config>.json`` -- the model: the ``repro.configs``
  name, overrides, chips and mesh, base ``EngineConfig`` fields, the
  weight init, and its source and departures from it;
* ``bench/traffic/<mix>.json`` -- the traffic parameters for
  ``bench/traffic_gen.py``, the engine sizing the mix is served with,
  and how many served tokens the check compares;
* ``bench/limits/<cell>.json`` -- the limit of each number the check
  compares, with the readings it was set from;
* ``bench/metrics/<metric>.py`` -- one reader per metric: ``read(rec)``
  returns the value, or ``None`` where the run has nothing to read.

A run: set-up (weights from the seed, the engine, a warm-up of every
shape the mix sends), one ``Engine.run`` over the whole schedule (the
window), then the reference check over a sample of what was served.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    mix_name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(path.parents[2])}")
    return json.loads(path.read_text())


def load_cell(name: str, bench_dir: Path = BENCH,
              spec_path: Optional[Path] = None) -> Cell:
    spec_path = spec_path or bench_dir.parent / "BENCHMARK.json"
    if not spec_path.is_file():
        raise BenchError(f"no {spec_path.name} beside {bench_dir.name}/")
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(
        name=name, config_name=w["config"], mix_name=w["traffic"],
        chips=int(w["chips"]),
        config=_read_json(bench_dir / "configs" / f"{w['config']}.json"),
        mix=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(bench_dir / "limits" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, bench_dir: Path = BENCH):
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path.name} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(n: int, require_tpu: bool = True):
    """The chips the cell asks for, or exit: there is no CPU branch."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


class CompileLog:
    """Counts XLA compilations (persistent-cache loads included) from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.n += 1


def seed_key(seed: int, stream: int) -> int:
    """A 31-bit key for JAX from any whole-number seed."""
    import numpy as np

    return int(np.random.default_rng([int(seed) % (1 << 63), stream])
               .integers(0, 2 ** 31 - 1))


@dataclasses.dataclass
class Record:
    """What a run leaves for the metric readers."""

    arch: dict            # model sizes (n_layers, d_model, ...)
    chips: int
    peaks: dict           # bench/peaks.json entry of this device kind
    outs: Any             # ServeResult: rid -> GenerationResult
    serve: Any            # ServeMetrics
    window_s: float       # engine clock from window start to last finish
    setup_s: float
    tracer_events: Optional[list] = None
    trace: Optional[dict] = None  # bench/trace.py summary


def arch_sizes(cfg, config: dict) -> dict:
    """The sizes the benchmark makes weights of: the program's, with the
    head tied where the configuration file ``config`` says so."""
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
            "tie_word_embeddings": bool(config.get("tie_word_embeddings"))}


def build_cfg(cell: Cell, extra: Optional[dict] = None):
    from repro import configs

    over = dict(cell.config.get("overrides", {}))
    over.update(extra or {})
    return configs.get_config(cell.config["arch"], **over)


def _check_layout(cfg, params) -> None:
    """The benchmark's weights must have the pytree the program takes."""
    import jax

    from repro.models import api

    want = jax.eval_shape(lambda k: api.init(cfg, k), jax.random.key(0))
    got = jax.eval_shape(lambda: params)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or [a.shape for a in jax.tree.leaves(want)]
            != [a.shape for a in jax.tree.leaves(got)]):
        raise BenchError("the program's parameter layout differs from "
                         "bench/weights.py; the reference cannot read it")


def make_requests(cell: Cell, items, seed: int, seconds: float, vocab: int):
    from repro.serving import Request, SamplingParams

    from bench import traffic_gen

    toks = traffic_gen.prompt_tokens(items, seed, vocab)
    end = seconds + float(cell.mix.get("drain_s", 0.0))
    return [Request(rid=it.rid, prompt=tk, max_new_tokens=it.max_new,
                    arrival_time=it.arrival_s,
                    sampling=SamplingParams(
                        deadline_ms=(end - it.arrival_s) * 1e3))
            for it, tk in zip(items, toks)]


@dataclasses.dataclass
class Setup:
    """A built cell: configuration, weights, engine and devices."""

    cfg: Any
    arch: dict
    params: Any
    engine: Any
    devs: list
    tracer: Any
    comp: CompileLog


def set_up(cell: Cell, seed: int, warm_lengths, *, trace: bool = False,
           require_tpu: bool = True, cfg_extra: Optional[dict] = None,
           log=None) -> Setup:
    """Weights from the seed, the engine, and a warm-up of the decode
    tick and of every prompt length in ``warm_lengths``.  ``cfg_extra``
    changes the configuration (the control runs the program's int8 path
    through it)."""
    log = log or _stderr
    devs = devices(cell.chips, require_tpu)

    import jax
    import jax.numpy as jnp

    from repro.launch.jax_cache import use_persistent_cache
    from repro.obs.trace import Tracer
    from repro.serving import Engine, EngineConfig

    from bench import weights

    if require_tpu:
        cache_dir = use_persistent_cache()
        # every program goes to the cache, however fast it compiled, so
        # a second run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"compile cache: {cache_dir}")
    comp = CompileLog()

    cfg = build_cfg(cell, cfg_extra)
    _check_sizes(cell, cfg)
    arch = arch_sizes(cfg, cell.config)
    mesh = sh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.runtime import sharding as shr

        mesh = make_serving_mesh(cell.config["mesh"])
        sh = shr.tree_shardings(mesh, weights.abstract(arch))
    params = weights.make(
        arch, jax.random.key(seed_key(seed, 0)),
        std=float(cell.config["init_std"]),
        dtype=jnp.dtype(cfg.param_dtype), out_shardings=sh)
    _check_layout(cfg, params)

    tracer = Tracer(capacity=1 << 20) if trace else None
    ecfg = dict(cell.config.get("engine", {}))
    ecfg.update(cell.mix.get("engine", {}))
    engine = Engine(cfg, params, EngineConfig(
        seed=seed_key(seed, 4), tracer=tracer, **ecfg), mesh=mesh)
    engine.warmup(list(warm_lengths))
    if tracer is not None:
        tracer.clear()
    return Setup(cfg=cfg, arch=arch, params=params, engine=engine,
                 devs=devs, tracer=tracer, comp=comp)


@dataclasses.dataclass
class Window:
    """What one window served."""

    requests: list
    outs: Any
    serve: Any
    window_s: float
    compiles: int
    profile: Optional[Path]


def serve_window(st: Setup, cell: Cell, seed: int, seconds: float, *,
                 trace: bool = False, trace_dir: Optional[Path] = None,
                 before=None) -> Window:
    """The mix's whole schedule through one ``Engine.run``.  ``before``
    is called just before the window opens (it reads the set-up time)."""
    import jax

    from bench import traffic_gen

    items = traffic_gen.schedule(cell.mix, seed, seconds)
    requests = make_requests(cell, items, seed, seconds, st.cfg.vocab)
    profile = None
    if trace:
        profile = Path(trace_dir or BENCH / "out" / "trace")
        for f in sorted(profile.rglob("*"), reverse=True):
            (f.unlink() if f.is_file() else f.rmdir())
        jax.profiler.start_trace(str(profile))
    n0 = st.comp.n
    if before is not None:
        before()
    if trace:
        with jax.profiler.TraceAnnotation("bench_window"):
            outs, serve = st.engine.run(requests)
        jax.profiler.stop_trace()
    else:
        outs, serve = st.engine.run(requests)
    return Window(requests=requests, outs=outs, serve=serve,
                  window_s=serve.makespan_s, compiles=st.comp.n - n0,
                  profile=profile)


def peaks_for(dev) -> dict:
    """The chip's peaks (bench/peaks.json); an unknown kind is an error."""
    if dev.platform != "tpu":
        return {}
    table = json.loads((BENCH / "peaks.json").read_text())
    if dev.device_kind not in table:
        raise BenchError(f"no peaks for device kind {dev.device_kind!r} in "
                         f"bench/peaks.json")
    return table[dev.device_kind]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             trace_dir: Optional[Path] = None, log=None) -> dict:
    """Set-up, the window and the check of one run; returns the result
    line's object."""
    from bench import check, traffic_gen

    log = log or _stderr
    st = set_up(cell, seed, traffic_gen.used_prompt_lengths(cell.mix,
                                                            seconds),
                trace=trace, require_tpu=require_tpu, log=log)
    n_setup = st.comp.n
    t_window = []
    win = serve_window(st, cell, seed, seconds, trace=trace,
                       trace_dir=trace_dir,
                       before=lambda: t_window.append(time.perf_counter()))
    setup_s = t_window[0] - t_process
    print(f"compilations in the window: {win.compiles} (set-up: "
          f"{n_setup})", flush=True)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in st.devs)
    tracer_events = (list(st.tracer.events) if st.tracer is not None
                     else None)
    st.engine = None  # frees the program's state before the reference
    gc.collect()

    summary = None
    if trace:
        from bench import trace as trace_mod

        summary = trace_mod.summarize_dir(win.profile, n_chips=cell.chips)
    rec = Record(arch=st.arch, chips=cell.chips,
                 peaks=peaks_for(st.devs[0]), outs=win.outs,
                 serve=win.serve, window_s=win.window_s, setup_s=setup_s,
                 tracer_events=tracer_events, trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t_check = time.perf_counter()
    verdict = check.compare(st.params, cell, win.requests, win.outs, seed)
    log(f"reference check: {time.perf_counter() - t_check:.1f} s")
    dev = st.devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(st.devs), "memory_peak_bytes": int(peak)}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result = {"correct": verdict["correct"], "attempted": len(win.requests),
              "failed": sum(1 for o in win.outs.values()
                            if check.is_failure(cell, o)),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def _stderr(*a):
    print(*a, file=sys.stderr, flush=True)


# published config.json keys -> the program's ArchConfig fields
_SIZE_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_hidden_layers": "n_layers",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
              "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
              "max_position_embeddings": "max_seq",
              "tie_word_embeddings": "tie_embeddings",
              "embedding_multiplier": "embedding_multiplier",
              "attention_multiplier": "attention_multiplier",
              "residual_multiplier": "residual_multiplier",
              "logits_scaling": "logits_scaling"}


def _check_sizes(cell: Cell, cfg) -> None:
    """The configuration file states what runs: its published keys must
    match the program's configuration, and a key whose arithmetic the
    program has no field for (so the reference would compute what the
    program leaves out) stops set-up."""
    for key, field in _SIZE_KEYS.items():
        if key not in cell.config:
            continue
        if not hasattr(cfg, field):
            raise BenchError(f"{cell.config_name}: the file states {key}="
                             f"{cell.config[key]!r} but the program's "
                             f"configuration has no field {field!r}")
        if cell.config[key] != getattr(cfg, field):
            raise BenchError(f"{cell.config_name}: {key}="
                             f"{cell.config[key]!r} but the program runs "
                             f"{field}={getattr(cfg, field)!r}")
