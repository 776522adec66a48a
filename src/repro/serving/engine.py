"""Continuous-batching serving engine.

One resident decode step serves a churning pool of requests — the
distributed-systems echo of the paper's feedback datapath (one reused
multiplier, many operands in flight; Lunglmayr's non-sequential divider
makes the same throughput argument at the FPGA level).  The loop:

    admission queue -> slot scheduler -> mixed prefill/decode ticks
                    -> completion / slot eviction

* **Prefill** runs per request at its own prompt length (one lowering per
  distinct length) and grafts the batch-1 state into a
  :class:`~repro.serving.cache.CachePool` row; the first token is
  sampled from the prefill logits (that timestamp is TTFT).
* **Decode ticks** run ONE fused jitted step over the whole pool with a
  per-slot ``cur_index`` vector; sampling (greedy / temperature /
  per-request top-k through the Goldschmidt softmax) happens inside the
  jit, so only the (n_slots,) chosen token ids cross to the host per
  tick.
* Finished requests free their slot and the next queued request takes
  it mid-flight; recycling cannot leak stale state because the prefill
  graft replaces the unmasked leaves (SSM/conv/cross-KV) whole and the
  decode mask hides KV rows beyond ``cur_index`` (see cache.py).

The pool is chosen by ``EngineConfig.pool``:

* ``"slot"`` — per-slot max-length rows (:class:`SlotCachePool`).
* ``"paged"`` — the block-table page arena (:class:`PagedCachePool`):
  admission reserves only the **prompt footprint**
  (``ceil(prompt/page_size)`` pages; ``page_reserve='worst'`` restores
  the old prompt+gen-1 budget) and the run loop appends pages as each
  slot's ``cur`` crosses a page boundary, so early-stopped requests
  never strand reservation; mid-decode arena exhaustion routes through
  the same preempt-youngest / AdmissionError machinery as refused
  admission.  The fused tick reads/writes KV through a
  ``(n_slots, pages_per_slot)`` block-table operand, and hash-keyed
  prefix sharing lets identical prompts prefill once and decode off
  shared pages; with ``prefix='pages'`` prefill runs in page-size
  chunks and partial hits resume from the deepest shared boundary
  bit-exactly.  A freed slot's table row points at the reserved trash
  page, so the stale writes the tick issues for inactive slots are
  harmless.  Greedy fp32 output is token-for-token identical to the
  slot pool (tests/test_serving.py::TestPagedServing).

``scheduler='static'`` degrades the same machinery to lockstep batching
(admit a full group, no admission until the whole group finishes) — the
baseline ``BENCH_serve.json`` compares against.

Scheduler-invariant sampling
----------------------------
The PRNG stream for token ``t`` of request ``r`` is
``fold_in(fold_in(key(seed), r), prompt_len + t)`` — a pure function of
(engine seed, request id, absolute sequence position).  Slot assignment,
pool width, admission order and the continuous/static scheduler choice
therefore cannot change a stochastic request's tokens: the same trace
under ``n_slots=1`` and ``n_slots=8``, continuous or static, yields
identical streams (tests/test_serving.py::TestSchedulerDeterminism).
Per-row keys are folded *inside* the fused tick from the (rid, cur)
vectors, so the scheme costs no extra host transfers.

Tensor-parallel serving
-----------------------
Pass ``mesh`` (axes ``("data", "model")``, launch/mesh.py) and the
engine runs the whole stack sharded: params are placed by the training
rule table (runtime/sharding.py), the pool by the decode-cache policy
(slots — or arena pages — over 'data', KV head_dim and SSM d_inner over
'model'), and the fused tick is jitted with matching in/out shardings so
the donated cache round-trips with **no resharding** — per-slot decode,
the Goldschmidt softmax sampler and admission grafts all stay on-device
across the mesh; only the (n_slots,) token ids cross to the host, as on
one device.  Greedy fp32 output is token-for-token identical to the
unsharded engine (tests/test_multidevice.py).

Caveat: MoE capacity grouping couples batch rows (tokens from different
slots compete for expert capacity), so engine outputs for MoE archs can
diverge from sequential runs when groups fill up — raise
``capacity_factor`` for strict parity, as the decode-consistency tests
do.  Dense / SSM / encdec rows are independent and match token-for-token
(greedy, fp32).

Fault tolerance
---------------
The run loop is built to contain the faults a fleet actually sees
(serving/resilience.py has the containment model; README the failure
table):

* **Deadlines** — ``SamplingParams.deadline_ms`` bounds arrival->finish
  on the engine clock; expiry is checked while queued (zero tokens) and
  after every tick (partial tokens kept), finishing the request with
  ``finish_reason="deadline"`` and releasing its slot/pages exactly.
* **Cancellation** — ``Engine.cancel(rid)`` marks a request; the next
  tick boundary finishes it with ``finish_reason="cancelled"`` wherever
  it is (pending/queued/active) with the same exact release.
* **NaN/Inf quarantine** — with ``numeric_guard`` (default on) the
  fused tick reduces a per-slot ``all(isfinite(logits))`` flag and
  folds it into the token array as sentinel ``-1`` (the flag rides the
  existing per-tick transfer); a tripped slot is freed
  and failed with ``finish_reason="numeric_error"`` in the same tick,
  while co-scheduled slots keep token-for-token parity (row-wise math +
  finite-NEG_INF masking — tests/test_serving_chaos.py).
* **Backpressure + retries** — ``max_queue`` bounds the admission
  queue; an arrival that finds it full retries with backoff up to
  ``max_retries`` times, then fails with ``finish_reason="rejected"``.
  Scripted tick failures (:class:`~repro.runtime.failures.TickFailure`)
  retry on the same budget.
* **Preemption over deadlock** — when the paged arena can't fit the
  head of line for ``preempt_after_ticks`` consecutive ticks, the
  youngest active request is preempted (pages freed, re-queued, later
  replayed from its recorded tokens — the (rid, position) PRNG keying
  makes stochastic replay exact); if nothing is active and nothing can
  ever free, the loop raises a typed
  :class:`~repro.serving.resilience.AdmissionError` with pool stats.
* **Chaos harness** — ``EngineConfig.injector``
  (:class:`~repro.runtime.failures.ServeFaultInjector`) scripts tick
  exceptions, slot NaN poison, arena squeezes and clock skew per tick,
  deterministic enough to gate unaffected-request parity in CI.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels.tuning import dispatch as _dispatch
from repro.launch.steps import (make_chunk_init_step, make_chunk_prefill_step,
                                make_decode_step, make_prefill_step)
from repro.obs import hlo
from repro.obs.metrics import summarize as _summarize
from repro.obs.trace import ENGINE_TRACK
from repro.layers.quant import quantize_params
from repro.models import api
from repro.runtime import sharding as shr
from repro.runtime.failures import TickFailure
from repro.serving.cache import (CachePool, PagedCachePool, SlotCachePool,
                                 _strip_paged, make_paged_cache,
                                 remap_kv_leaves)
from repro.serving.requests import (FINISH_CANCELLED, FINISH_DEADLINE,
                                    FINISH_NUMERIC, FINISH_REJECTED,
                                    FINISHED, QUEUED, RUNNING,
                                    GenerationResult, Request, RequestState,
                                    SamplingParams, ServeResult)
from repro.serving.resilience import AdmissionError, poison_slot_cache
from repro.serving.sampler import sample_tokens

SCHEDULERS = ("continuous", "static")
POOLS = ("slot", "paged")

# what a host phase costs with no tracer attached: one shared, reusable
# context manager that does nothing (no allocation per phase)
_NO_PHASE = contextlib.nullcontext()


def prefill_batch(cfg: ArchConfig, req: Request) -> dict:
    """Batch-1 prefill inputs for one request (tokens, mrope ids, frames).

    Shared by the engine and the sequential parity reference so the two
    can never diverge on input construction.
    """
    batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
    if cfg.pos == "mrope":
        batch["pos_ids"] = jnp.broadcast_to(
            jnp.arange(req.prompt_len, dtype=jnp.int32),
            (3, 1, req.prompt_len))
    if req.frames is not None:
        batch["frames"] = jnp.asarray(req.frames, cfg.dtype)[None]
    return batch


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4
    s_max: int = 0  # 0 -> cfg.max_seq
    max_prefill_per_tick: int = 1  # prefills admitted between decode ticks
    top_k: int = 0  # default top-k for requests whose SamplingParams has 0
    seed: int = 0   # PRNG stream for stochastic sampling
    pool: str = "slot"      # slot | paged
    page_size: int = 16     # paged: tokens per arena page
    n_pages: int = 0        # paged: arena size; 0 -> worst case + trash
    prefix: str = "exact"   # paged: prefix sharing — exact | pages | off
    page_reserve: str = "prompt"  # paged: prompt | worst admission budget
    # -- fault tolerance (module docstring, "Fault tolerance") --
    numeric_guard: bool = True  # per-slot NaN/Inf quarantine in the tick
    max_queue: int = 0          # bounded admission queue; 0 = unbounded
    max_retries: int = 2        # submit retries on overflow + tick retries
    retry_backoff_s: float = 0.01
    preempt_after_ticks: int = 3  # paged: stalled-head ticks before preempt
    injector: Optional[Any] = None  # ServeFaultInjector (eq=False: hashable)
    # -- observability (repro.obs; README "Observability") --
    tracer: Optional[Any] = None  # obs.Tracer: request-lifecycle tracing


@dataclasses.dataclass
class ServeMetrics:
    n_requests: int = 0
    prefill_tokens: int = 0   # prompt tokens processed by prefill
    prefill_skips: int = 0    # prefills skipped via exact prefix hits
    first_tokens: int = 0     # tokens sampled from prefill(-cache) logits
    decode_tokens: int = 0    # tokens sampled from decode ticks
    decode_ticks: int = 0
    prefill_time_s: float = 0.0
    decode_time_s: float = 0.0
    occupancy_ticks: int = 0  # sum over ticks of active slots
    peak_active: int = 0      # max concurrently active slots in any tick
    n_slots: int = 0
    makespan_s: float = 0.0   # first admission -> last completion
    ttft_s: Dict[int, float] = dataclasses.field(default_factory=dict)
    # raw latency samples (seconds); to_dict summarizes them into the
    # "ttft"/"itl" p50/p95/p99 blocks (repro.obs.metrics.summarize)
    ttft_samples: List[float] = dataclasses.field(default_factory=list)
    itl_samples: List[float] = dataclasses.field(default_factory=list)
    prefix_hits: int = 0        # admissions served (fully or partly) shared
    prefix_hit_tokens: int = 0  # prompt tokens covered by shared pages
    pool: dict = dataclasses.field(default_factory=dict)  # pool.stats()
    # -- failure accounting --
    failed: int = 0        # numeric_error + rejected terminal failures
    cancelled: int = 0     # Engine.cancel took effect
    timed_out: int = 0     # deadline_ms expired (queued or mid-decode)
    preempted: int = 0     # paged preempt-youngest events
    retried: int = 0       # submit retries + tick retries consumed
    kernel_fallbacks: int = 0  # pallas->jnp downgrades during this run
    # collective ops one decode tick runs, and their bytes per chip, read
    # from the compiled tick's HLO (repro.obs.hlo); 0 without a mesh
    tick_collectives: int = 0
    tick_collective_bytes: int = 0
    # per-kernel attribution: which kernel downgraded (not just how many
    # times in total), plus the dispatch-layer resolve / autotune-cache
    # hit/miss deltas for the run (kernels/tuning/dispatch.py)
    kernel_fallbacks_by_kernel: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    dispatch: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)

    @property
    def decode_tok_per_s(self) -> float:
        if self.decode_ticks == 0:  # e.g. every request had --gen 1
            return 0.0
        return self.decode_tokens / max(self.decode_time_s, 1e-9)

    @property
    def aggregate_tok_per_s(self) -> float:
        """Useful generated tokens over the whole serve wall time — the
        scheduler-level throughput (what continuous batching improves)."""
        if self.makespan_s <= 0:
            return 0.0
        return (self.first_tokens + self.decode_tokens) / self.makespan_s

    @property
    def occupancy(self) -> float:
        """Mean fraction of pool slots doing useful work per decode tick."""
        if self.decode_ticks == 0:
            return 0.0
        return self.occupancy_ticks / (self.decode_ticks * self.n_slots)

    @property
    def ttft_summary(self) -> dict:
        """TTFT distribution: count/mean/min/max/p50/p95/p99 seconds."""
        return _summarize(self.ttft_samples)

    @property
    def itl_summary(self) -> dict:
        """Inter-token latency distribution (time between consecutive
        tokens of one request, decode ticks only), seconds."""
        return _summarize(self.itl_samples)

    # derived keys to_dict adds on top of the dataclass fields; from_dict
    # strips exactly these, so the pair stays a lossless round trip
    _DERIVED = ("decode_tok_per_s", "aggregate_tok_per_s", "occupancy",
                "ttft", "itl")

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["decode_tok_per_s"] = self.decode_tok_per_s
        d["aggregate_tok_per_s"] = self.aggregate_tok_per_s
        d["occupancy"] = self.occupancy
        d["ttft_s"] = {str(k): v for k, v in self.ttft_s.items()}
        d["ttft"] = self.ttft_summary
        d["itl"] = self.itl_summary
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ServeMetrics":
        """Inverse of :meth:`to_dict` (derived summary keys dropped,
        ``ttft_s`` rid keys back to int) — the JSON round trip tests
        and offline tooling rebuild metrics through this."""
        d = dict(d)
        for k in cls._DERIVED:
            d.pop(k, None)
        d["ttft_s"] = {int(k): v for k, v in d.get("ttft_s", {}).items()}
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(f"unknown ServeMetrics keys: {sorted(unknown)}")
        return cls(**d)


class Engine:
    """Continuous-batching engine over one model + one cache pool.

    ``mesh`` (optional) runs the whole stack tensor/data-parallel over a
    ``("data", "model")`` device mesh — see the module docstring.
    """

    def __init__(self, cfg: ArchConfig, params,
                 engine_cfg: Optional[EngineConfig] = None, *,
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.ecfg = engine_cfg or EngineConfig()
        if self.ecfg.pool not in POOLS:
            raise ValueError(f"pool must be one of {POOLS}")
        if self.ecfg.page_reserve not in ("prompt", "worst"):
            raise ValueError("page_reserve must be prompt|worst, got "
                             f"{self.ecfg.page_reserve}")
        self.s_max = self.ecfg.s_max or cfg.max_seq
        self.mesh = mesh
        self._policy = cfg.policy()
        self._paged = self.ecfg.pool == "paged"
        self._pages_per_slot = -(-self.s_max // self.ecfg.page_size)
        self._n_pages = self.ecfg.n_pages or (
            self.ecfg.n_slots * self._pages_per_slot + 1)
        # cfg.quant != "none" turns on the quantized datapath: params go
        # int8 in HBM (dequantized transiently inside the jitted steps,
        # launch/steps.py) and the KV arena leaves of either pool go int8
        # on the static KV scale (core/formats.py).  Leaf names and ranks
        # are unchanged, so the sharding rule tables apply as-is.
        self._kv_dtype = jnp.int8 if cfg.quant != "none" else None
        if cfg.quant != "none":
            params = quantize_params(params)
        if mesh is None:
            self.params = params
            self._dp = ()
            self._param_sh = self._cache_sh = None
        else:
            # Params by the training rule table; the pool by the decode-
            # cache policy.  Prefill is batch-1 (no dp axis to use), the
            # tick batches over the pool, so only the tick gets dp axes.
            self._dp = shr.dp_axes(mesh, self.ecfg.n_slots)
            self._param_sh = shr.tree_shardings(
                mesh, jax.eval_shape(lambda: params))
            self.params = jax.device_put(params, self._param_sh)
            if self._paged:
                cache_specs = jax.eval_shape(lambda: make_paged_cache(
                    cfg, self.ecfg.n_slots, self._n_pages,
                    self.ecfg.page_size, jnp.dtype(cfg.dtype),
                    kv_dtype=self._kv_dtype))
            else:
                cache_specs = jax.eval_shape(lambda: remap_kv_leaves(
                    api.make_cache(cfg, self.ecfg.n_slots, self.s_max,
                                   jnp.dtype(cfg.dtype)), self._kv_dtype))
            self._cache_sh = shr.pool_shardings(
                mesh, cfg, cache_specs, self.ecfg.n_slots)
        self._prefill = jax.jit(make_prefill_step(cfg, mesh=mesh, dp=()))
        # chunked prefill: only the pages-sharing paged engine runs it —
        # its fixed page-size chunk schedule is what makes partial-hit
        # resume bit-exact (cold and resumed prefills share every
        # compiled (prefix, chunk) artifact); exact/off keep the one-shot
        # flash prefill whose output matches the sequential reference
        self._chunked = self._paged and self.ecfg.prefix == "pages"
        self._chunk_init = jax.jit(make_chunk_init_step(cfg, mesh=mesh,
                                                        dp=()))
        self._chunk_prefill = jax.jit(
            make_chunk_prefill_step(cfg, mesh=mesh, dp=()))
        self._decode = make_decode_step(
            cfg, mesh=mesh, dp=self._dp,
            page_size=self.ecfg.page_size if self._paged else 0)
        self._tick_fns: Dict[tuple, object] = {}
        self._collectives: Dict[int, Tuple[int, int]] = {}
        self._first_fns: Dict[tuple, object] = {}
        self._key = jax.random.key(self.ecfg.seed)
        self._cancel_rids: set = set()
        # host-side twin of the tick's validity reduce, for prefill logits
        self._finite_fn = jax.jit(lambda lg: jnp.all(
            jnp.isfinite(lg[:, -1, :].astype(jnp.float32))))

    def cancel(self, rid: int) -> None:
        """Mark ``rid`` for cancellation; the run loop finishes it with
        ``finish_reason="cancelled"`` at the next tick boundary (pending,
        queued and active requests alike), releasing its slot/pages
        exactly.  Unknown rids are ignored at run end."""
        self._cancel_rids.add(rid)

    def _make_pool(self) -> CachePool:
        if self._paged:
            return PagedCachePool(
                self.cfg, self.ecfg.n_slots, self.s_max,
                jnp.dtype(self.cfg.dtype), page_size=self.ecfg.page_size,
                n_pages=self._n_pages, share=self.ecfg.prefix,
                reserve=self.ecfg.page_reserve,
                mesh=self.mesh, shardings=self._cache_sh,
                kv_dtype=self._kv_dtype, tracer=self.ecfg.tracer)
        return SlotCachePool(self.cfg, self.ecfg.n_slots, self.s_max,
                             jnp.dtype(self.cfg.dtype), mesh=self.mesh,
                             shardings=self._cache_sh,
                             kv_dtype=self._kv_dtype,
                             tracer=self.ecfg.tracer)

    def _effective_k(self, req: Request) -> int:
        return req.sampling.top_k or self.ecfg.top_k

    # -- fused jitted steps --------------------------------------------------

    def _tick_fn(self, stochastic: bool, max_top_k: int = 0,
                 guard: bool = False):
        """The fused pool-wide decode tick, compiled per
        (stochastic, max top-k bound, numeric-guard flag); paged engines
        thread the block table as one extra device operand.  With
        ``guard`` the tick folds the per-slot
        ``all(isfinite(final logits))`` reduce into the token array as
        sentinel ``-1`` — the NaN-quarantine flag rides the existing
        (n_slots,) transfer, costing only a vocab-width reduce."""
        fkey = (stochastic, max_top_k, guard)
        if fkey not in self._tick_fns:
            cfg, policy = self.cfg, self._policy
            decode, paged = self._decode, self._paged

            def sample(logits, cur_index, temps, topks, rids, key):
                if stochastic:
                    # per-row streams keyed on (request, position): the
                    # token being sampled sits at absolute position
                    # cur_index + 1 (see "Scheduler-invariant sampling")
                    keys = jax.vmap(lambda r, c: jax.random.fold_in(
                        jax.random.fold_in(key, r), c + 1))(rids, cur_index)
                else:
                    keys = None
                return sample_tokens(
                    logits[:, -1, :], policy=policy,
                    temperature=temps if stochastic else 0.0,
                    top_k=topks if max_top_k else 0,
                    max_top_k=max_top_k or None, key=keys)

            def step_for(tokens, cur_index):
                step = {"token": tokens}
                if cfg.pos == "mrope":
                    # text-style positions: the three streams coincide
                    step["pos_ids"] = jnp.broadcast_to(
                        cur_index[None, :, None], (3, tokens.shape[0], 1))
                return step

            def emit(logits, toks):
                if not guard:
                    return toks
                # fold the validity flag into the token array as sentinel
                # -1 (token ids are always >= 0): the guarded tick keeps
                # a single (n_slots,) output, so the guard costs one
                # vocab-width isfinite reduce + a where — no second
                # device->host transfer, same out_sharding as unguarded.
                # XLA fuses the reduce into the head's matmul, so it
                # shares the head's scope
                with jax.named_scope("lm_head"):
                    valid = jnp.all(
                        jnp.isfinite(logits[:, -1, :].astype(jnp.float32)),
                        axis=-1)
                return jnp.where(valid, toks, -1)

            if paged:
                def tick(params, cache, table, cur_index, tokens, temps,
                         topks, rids, key):
                    logits, cache = decode(params, cache, cur_index,
                                           step_for(tokens, cur_index),
                                           page_table=table)
                    return emit(logits, sample(logits, cur_index, temps,
                                               topks, rids, key)), cache
            else:
                def tick(params, cache, cur_index, tokens, temps, topks,
                         rids, key):
                    logits, cache = decode(params, cache, cur_index,
                                           step_for(tokens, cur_index))
                    return emit(logits, sample(logits, cur_index, temps,
                                               topks, rids, key)), cache

            jit_kw = {}
            if self.mesh is not None:
                n_ops = 7 if paged else 6
                repl = NamedSharding(self.mesh, P())
                jit_kw = dict(
                    in_shardings=(self._param_sh, self._cache_sh) +
                                 (None,) * n_ops,
                    out_shardings=(repl, self._cache_sh))
            self._tick_fns[fkey] = jax.jit(
                tick, donate_argnums=(1,), **jit_kw)
        return self._tick_fns[fkey]

    def _tick_collectives(self, tick, args) -> Tuple[int, int]:
        """(collective ops, their bytes per chip) of one execution of the
        jitted tick ``tick`` on ``args``, read from its compiled HLO the
        first time it is asked for.  JAX keeps that lowering and its
        executable, so the tick's own call traces and compiles nothing
        more.  (0, 0) without a mesh."""
        if self.mesh is None:
            return (0, 0)
        if id(tick) not in self._collectives:
            self._collectives[id(tick)] = hlo.summary(
                tick.lower(*args).compile().as_text())
        return self._collectives[id(tick)]

    def _first_fn(self, stochastic: bool, top_k: int = 0):
        fkey = (stochastic, top_k)
        if fkey not in self._first_fns:
            policy = self._policy

            def first(logits, temp, key):
                return sample_tokens(
                    logits[:, -1, :], policy=policy,
                    temperature=temp if stochastic else 0.0, top_k=top_k,
                    key=key if stochastic else None)

            self._first_fns[fkey] = jax.jit(first)
        return self._first_fns[fkey]

    def _request_key(self, rid: int, pos: int):
        """Key for the token at absolute position ``pos`` of request
        ``rid`` — the host-side twin of the tick's in-jit fold."""
        return jax.random.fold_in(
            jax.random.fold_in(self._key, jnp.int32(rid)), jnp.int32(pos))

    # -- request plumbing ----------------------------------------------------

    def _validate(self, req: Request) -> None:
        if req.prompt_len + req.max_new_tokens - 1 > self.s_max:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + gen "
                f"{req.max_new_tokens} exceeds s_max={self.s_max}")
        if self._paged:
            total = req.prompt_len + req.max_new_tokens - 1
            need = -(-total // self.ecfg.page_size)
            if need > self._n_pages - 1:
                raise ValueError(
                    f"request {req.rid}: needs {need} pages but the arena "
                    f"only has {self._n_pages - 1} (plus the trash page)")
        if self.cfg.family == "encdec" and req.frames is None:
            raise ValueError(f"request {req.rid}: encdec needs frames")

    def _run_chunked_prefill(self, pool: CachePool, eff: Request,
                             hit, metrics: ServeMetrics):
        """Prefill ``eff`` in page-size chunks, resuming from the deepest
        shared-page boundary when the admission's PrefixHit carries one.

        Returns (last-position logits, final carry, boundaries) where
        ``boundaries`` maps prompt page index -> (logits, stripped
        carry) snapshots taken as each full page completes — the pool
        publishes them with the page entries so later partial hits can
        resume here.  The chunk schedule depends only on (start, chunk
        length), never on the total prompt, so a resumed prefill reuses
        the cold run's compiled artifacts and is bit-exact against it.
        """
        ps = self.ecfg.page_size
        plen = eff.prompt_len
        tr = self.ecfg.tracer
        resume = hit is not None and hit.resume is not None
        if resume:
            start = hit.resume_tokens
            logits = hit.resume.logits
            states = pool.resume_state(hit)
            if tr is not None:
                tr.instant("prefix_resume", ("req", eff.rid), tokens=start)
        else:
            start = 0
            logits = None
            states = self._chunk_init(self.params,
                                      prefill_batch(self.cfg, eff))
        boundaries: Dict[int, tuple] = {}
        pos = start
        while pos < plen:
            end = min(pos + ps, plen)
            chunk = {"tokens": jnp.asarray(eff.prompt[None, pos:end],
                                           jnp.int32)}
            if self.cfg.pos == "mrope":
                chunk["pos_ids"] = jnp.broadcast_to(
                    jnp.arange(pos, end, dtype=jnp.int32), (3, 1, end - pos))
            logits, states = self._chunk_prefill(self.params, states, chunk,
                                                 jnp.int32(pos))
            if end % ps == 0:
                # stripped: the snapshot keeps only the non-paged leaves
                # (conv/ssm/cross-KV) — the KV prefix itself lives in the
                # shared pages and is re-gathered at resume
                boundaries[end // ps - 1] = (logits, _strip_paged(states))
            pos = end
        metrics.prefill_tokens += plen - start
        return logits, states, boundaries

    def _effective_request(self, st: RequestState) -> Request:
        """The request as it would prefill right now: a preemption replay
        folds its recorded tokens (all but the held last one) into the
        prompt.  Admission gates on this, not the original request —
        under prompt-only page reservation a replay's footprint grows
        with its recorded tokens, so gating on the original prompt would
        admit a replay the alloc cannot satisfy."""
        req = st.request
        if not st.tokens:
            return req
        prompt = (np.concatenate([req.prompt,
                                  np.asarray(st.tokens[:-1], np.int32)])
                  if len(st.tokens) > 1 else req.prompt)
        return Request(rid=req.rid, prompt=prompt,
                       max_new_tokens=(req.max_new_tokens
                                       - len(st.tokens) + 1),
                       sampling=req.sampling, frames=req.frames)

    def _do_prefill(self, st: RequestState, eff: Request, pool: CachePool,
                    metrics: ServeMetrics, clock) -> bool:
        """Admit ``st`` into a slot as ``eff`` (its
        :meth:`_effective_request`).  Returns False when the request was
        failed instead (non-finite prefill logits under the numeric
        guard) — the slot is already released.

        A state that carries tokens is a **preemption replay**: its
        prompt + all-but-the-last recorded token re-prefill as one
        prompt (the worst-case footprint prompt+gen-1 is invariant, and
        the same ``cur_index``), the held last token re-enters decode,
        and no first token is sampled.  The (rid, absolute position) PRNG
        keying makes the remaining stochastic stream identical to the
        un-preempted run.
        """
        req = st.request
        sp = req.sampling
        stochastic = sp.stochastic
        tr = self.ecfg.tracer
        tc0 = clock() if tr is not None else 0.0
        replay = len(st.tokens) > 0
        t0 = time.perf_counter()
        # alloc first: a paged pool resolves prefix hits here, and a
        # whole-prompt hit means the prefill never runs at all
        slot = pool.alloc(eff)
        hit = getattr(slot, "hit", None)
        boundaries = None
        if hit is not None and hit.skip_prefill:
            logits, states = hit.entry.logits, None
            metrics.prefill_skips += 1
        elif self._chunked:
            logits, states, boundaries = self._run_chunked_prefill(
                pool, eff, hit, metrics)
        else:
            logits, states, _ = self._prefill(self.params,
                                              prefill_batch(self.cfg, eff))
            metrics.prefill_tokens += eff.prompt_len
        if self.ecfg.numeric_guard and not bool(self._finite_fn(logits)):
            # poisoned prefill: fail before the write so the prefix
            # index never caches non-finite logits/states
            pool.free(int(slot))
            metrics.prefill_time_s += time.perf_counter() - t0
            st.reason = FINISH_NUMERIC
            st.status = FINISHED
            st.t_finish = clock()
            metrics.failed += 1
            if tr is not None:
                tr.span("prefill", ("req", req.rid), tc0,
                        hit=bool(hit and hit.skip_prefill), replay=replay,
                        poisoned=True)
                tr.instant("quarantine", ("req", req.rid), where="prefill")
                self._trace_finish(st)
            return False
        if not replay:
            first = self._first_fn(stochastic, self._effective_k(req))(
                logits, jnp.float32(sp.temperature),
                self._request_key(req.rid, req.prompt_len) if stochastic
                else self._key)
            token = int(jax.block_until_ready(first)[0])
        st.slot = int(slot)
        pool.write(st.slot, states, req=eff, logits=logits,
                   boundaries=boundaries)
        # settle the graft inside the prefill window so its async device
        # work isn't billed to the next decode tick's timing
        jax.block_until_ready(pool.cache)
        metrics.prefill_time_s += time.perf_counter() - t0
        st.status = RUNNING
        if tr is not None:
            tr.span("prefill", ("req", req.rid), tc0,
                    hit=bool(hit and hit.skip_prefill), replay=replay,
                    prompt_len=eff.prompt_len, slot=st.slot)
        if not replay:
            st.tokens.append(token)
            st.t_first_token = clock()
            st.t_last_token = st.t_first_token
            metrics.first_tokens += 1
            metrics.ttft_s[req.rid] = st.ttft
            metrics.ttft_samples.append(st.ttft)
            if tr is not None:
                tr.instant("first_token", ("req", req.rid),
                           t=st.t_first_token)
        return True

    def _finish(self, st: RequestState, pool: CachePool, clock) -> None:
        st.t_finish = clock()
        st.status = FINISHED
        pool.free(st.slot)
        st.slot = -1
        self._trace_finish(st)

    def _trace_finish(self, st: RequestState) -> None:
        """Close whichever lifecycle spans are open on the request's
        track and stamp the terminal ``finish`` instant (every finish
        path funnels through here, so the span-chain validator can
        require exactly one per request)."""
        tr = self.ecfg.tracer
        if tr is None:
            return
        track = ("req", st.request.rid)
        tr.end("queued", track)
        tr.end("decode", track)
        tr.instant("finish", track, t=st.t_finish,
                   reason=st.finish_reason, n_tokens=len(st.tokens))

    # -- the serve loop ------------------------------------------------------

    def run(self, requests: Sequence[Request], *,
            scheduler: str = "continuous") -> ServeResult:
        """Serve ``requests`` to completion.

        Returns a :class:`ServeResult` — a mapping ``rid ->``
        :class:`GenerationResult` that also unpacks as the legacy
        ``(outputs, metrics)`` pair.

        The engine clock is wall time from call start; a request with
        ``arrival_time`` in the future is invisible to the scheduler
        until the clock passes it (the loop sleeps when idle).
        Admission is FIFO: a head-of-line request the pool cannot fit
        yet waits for active slots to drain (page budget included).

        With a tracer attached, the loop's host phases (``admit``,
        ``prefill``, ``page_append``, ``tick_prepare``, ``tick_dispatch``,
        ``tick_wait``, ``emit``, ``idle``, all inside ``run``) are
        recorded as ``engine.<phase>`` spans that carry the decode tick
        number, and mirrored into the profiler's trace
        (:meth:`~repro.obs.trace.Tracer.phase`).
        """
        if scheduler not in SCHEDULERS:
            raise ValueError(f"scheduler must be one of {SCHEDULERS}")
        all_rids = [r.rid for r in requests]
        if len(set(all_rids)) != len(all_rids):
            raise ValueError("duplicate request rids: outputs are keyed "
                             "by rid")
        for req in requests:
            self._validate(req)
        n = self.ecfg.n_slots
        pool = self._make_pool()
        max_top_k = max((self._effective_k(r) for r in requests), default=0)
        metrics = ServeMetrics(n_requests=len(requests), n_slots=n)
        fb_start = _dispatch.fallback_stats()
        disp_start = _dispatch.dispatch_snapshot()
        t_start = time.perf_counter()
        skew = [0.0]  # injected clock-skew accumulator (list: closure write)
        clock = lambda: time.perf_counter() - t_start + skew[0]  # noqa: E731
        tr = self.ecfg.tracer
        if tr is not None:
            # trace timestamps ride the engine clock, skew included, so
            # the exported timeline moves with injected clock faults the
            # same way deadlines do
            tr.bind_clock(clock)
            tr.instant("run_start", ENGINE_TRACK, scheduler=scheduler,
                       n_slots=n, pool=self.ecfg.pool,
                       n_requests=len(requests))

        # the run phase opens at engine-clock ~0: one offset maps every
        # span of the run onto the profiler's clock
        mesh = ("x".join(str(v) for v in self.mesh.shape.values())
                if self.mesh is not None else None)
        with (tr.phase("run", mesh=mesh, chips=(self.mesh.size if mesh
                                                 else 1))
              if tr is not None else _NO_PHASE):
            states: List[RequestState] = [
                RequestState(r, t_arrive=r.arrival_time,
                             deadline_at=(
                                 r.arrival_time
                                 + r.sampling.deadline_ms / 1e3
                                 if r.sampling.deadline_ms is not None
                                 else float("inf")))
                for r in sorted(requests,
                                key=lambda r: (r.arrival_time, r.rid))]
            if tr is not None:
                for st in states:
                    tr.instant("submitted", ("req", st.request.rid),
                               t=st.t_arrive)
            self._serve(states, pool, metrics, clock, skew, scheduler,
                        max_top_k)

        self._cancel_rids.clear()
        fb_by_kernel = {
            k: v - fb_start.get(k, 0)
            for k, v in _dispatch.fallback_stats().items()
            if v - fb_start.get(k, 0)}
        metrics.kernel_fallbacks_by_kernel = fb_by_kernel
        metrics.kernel_fallbacks = sum(fb_by_kernel.values())
        metrics.dispatch = _dispatch.dispatch_delta(disp_start)
        metrics.makespan_s = clock()
        if tr is not None:
            # the compiled tick's collectives ride the run's last record
            # on the engine track: no record of their own
            tr.instant("run_end", ENGINE_TRACK,
                       decode_ticks=metrics.decode_ticks,
                       tick_collectives=metrics.tick_collectives,
                       tick_collective_bytes=metrics.tick_collective_bytes)
        stats = pool.stats()
        metrics.pool = stats
        metrics.prefix_hits = stats.get("prefix_hits", 0)
        metrics.prefix_hit_tokens = stats.get("prefix_hit_tokens", 0)
        outputs = {}
        for st in states:
            assert st.status == FINISHED, (st.request.rid, st.status)
            outputs[st.request.rid] = GenerationResult(
                rid=st.request.rid,
                prompt_len=st.request.prompt_len,
                tokens=np.asarray(st.tokens, np.int32),
                ttft_s=st.ttft if st.tokens else 0.0,
                finish_s=st.t_finish - st.t_arrive,
                finish_reason=st.finish_reason,
                metrics=metrics,
            )
        return ServeResult(outputs, metrics)

    def _serve(self, states: List[RequestState], pool: CachePool,
               metrics: ServeMetrics, clock, skew: List[float],
               scheduler: str, max_top_k: int) -> None:
        """:meth:`run`'s loop: admission, page appends and decode ticks
        until every state has finished."""
        n = self.ecfg.n_slots
        guard = self.ecfg.numeric_guard
        inj = self.ecfg.injector
        tr = self.ecfg.tracer
        # deques: the admission loop pops from the head every tick, and a
        # list.pop(0) there is O(n) — quadratic over a long Poisson trace
        pending: Deque[RequestState] = deque(states)
        ready: Deque[RequestState] = deque()
        active: Dict[int, RequestState] = {}  # slot -> state

        # host-side mirrors of the per-slot device vectors; finished
        # slots are zeroed (a paged pool's trash-page writes then always
        # target (page 0, offset 0) instead of wandering with stale cur)
        cur = np.zeros(n, np.int32)
        last_tok = np.zeros(n, np.int32)
        temps = np.zeros(n, np.float32)
        topks = np.zeros(n, np.int32)
        rids = np.zeros(n, np.int32)

        poison_queue: set = set()  # rids awaiting NaN poison (injector)
        stall = 0                  # consecutive refused-head passes
        admit_seq = [0]

        def admit_arrivals():
            now = clock()
            requeue: List[RequestState] = []
            while pending and pending[0].t_arrive <= now:
                st = pending.popleft()
                if self.ecfg.max_queue and len(ready) >= self.ecfg.max_queue:
                    # backpressure: the bounded queue is full — retry
                    # with backoff, then reject
                    if st.retries < self.ecfg.max_retries:
                        st.retries += 1
                        metrics.retried += 1
                        st.t_arrive = now + self.ecfg.retry_backoff_s
                        requeue.append(st)
                        if tr is not None:
                            tr.instant("retry_backoff",
                                       ("req", st.request.rid),
                                       attempt=st.retries)
                    else:
                        st.status = FINISHED
                        st.reason = FINISH_REJECTED
                        st.t_finish = clock()
                        metrics.failed += 1
                        self._trace_finish(st)
                    continue
                st.status = QUEUED
                ready.append(st)
                if tr is not None:
                    tr.begin("queued", ("req", st.request.rid))
            for s in requeue:
                # bisect insertion keeps pending sorted by (t_arrive, rid)
                # without re-sorting the whole deque per backoff requeue
                # (quadratic over a churning trace)
                bisect.insort(pending, s,
                              key=lambda x: (x.t_arrive, x.request.rid))

        def fail_waiting(store: Deque[RequestState], reason: str,
                         match) -> int:
            """Terminate matching not-yet-admitted states in place."""
            hits = 0
            keep = [s for s in store if not match(s)]
            for s in store:
                if match(s):
                    s.status = FINISHED
                    s.reason = reason
                    s.t_finish = clock()
                    hits += 1
                    self._trace_finish(s)
            store.clear()
            store.extend(keep)
            return hits

        def evict(slot: int, reason: Optional[str]) -> RequestState:
            """Remove an active slot; with a reason, finish its request."""
            st = active.pop(slot)
            if reason is not None:
                st.reason = reason
            if tr is not None:
                tr.end("resident", ("slot", slot))
            self._finish(st, pool, clock)
            clear(slot)
            return st

        def apply_cancels():
            if not self._cancel_rids:
                return
            hit = lambda s: s.request.rid in self._cancel_rids  # noqa: E731
            metrics.cancelled += fail_waiting(pending, FINISH_CANCELLED, hit)
            metrics.cancelled += fail_waiting(ready, FINISH_CANCELLED, hit)
            for slot, st in list(active.items()):
                if hit(st):
                    evict(slot, FINISH_CANCELLED)
                    metrics.cancelled += 1

        def expire_deadlines():
            now = clock()
            expired = lambda s: now > s.deadline_at  # noqa: E731
            # pending too: a backoff-requeued request sitting out its
            # retry window past deadline_ms must finish with
            # reason="deadline", not keep retrying toward "rejected"
            metrics.timed_out += fail_waiting(pending, FINISH_DEADLINE,
                                              expired)
            metrics.timed_out += fail_waiting(ready, FINISH_DEADLINE,
                                              expired)
            for slot, st in list(active.items()):
                if expired(st):
                    evict(slot, FINISH_DEADLINE)
                    metrics.timed_out += 1

        def start(st: RequestState):
            if tr is not None:
                tr.end("queued", ("req", st.request.rid))
            eff = self._effective_request(st)
            with (tr.phase("prefill", tick=tick_no, rid=st.request.rid,
                           prompt_len=eff.prompt_len)
                  if tr is not None else _NO_PHASE):
                ok = self._do_prefill(st, eff, pool, metrics, clock)
            if not ok:
                return  # failed at prefill (numeric guard); slot released
            st.admit_seq = admit_seq[0]
            admit_seq[0] += 1
            if st.done:  # max_new_tokens == 1: no decode steps at all
                self._finish(st, pool, clock)
                return
            if tr is not None:
                tr.begin("decode", ("req", st.request.rid))
                tr.begin("resident", ("slot", st.slot),
                         rid=st.request.rid)
            active[st.slot] = st
            cur[st.slot] = st.cur_index
            last_tok[st.slot] = st.tokens[-1]
            temps[st.slot] = st.request.sampling.temperature
            topks[st.slot] = self._effective_k(st.request)
            rids[st.slot] = st.request.rid

        def clear(slot: int):
            cur[slot] = 0
            last_tok[slot] = 0
            temps[slot] = 0.0
            topks[slot] = 0
            rids[slot] = 0

        def preempt_youngest():
            """Paged graceful degradation: free the most recently admitted
            request's pages and re-queue it behind the stalled head; its
            recorded tokens replay at re-admission (see _do_prefill)."""
            slot, st = max(active.items(),
                           key=lambda kv: kv[1].admit_seq)
            del active[slot]
            pool.free(slot)
            clear(slot)
            st.slot = -1
            st.status = QUEUED
            metrics.preempted += 1
            if tr is not None:
                track = ("req", st.request.rid)
                tr.end("decode", track)
                tr.end("resident", ("slot", slot))
                tr.instant("preempt", track, slot=slot)
                tr.begin("queued", track)
            ready.insert(min(1, len(ready)), st)

        while pending or ready or active:
            tick_no = metrics.decode_ticks
            if inj is not None:
                ev = inj.events_at(tick_no)
                if ev:
                    skew[0] += ev.get("skew", 0.0)
                    for rid in ev.get("cancel", ()):
                        self.cancel(rid)
                    if self._paged and ev.get("squeeze"):
                        pool.seize_pages(ev["squeeze"])
                    if self._paged and ev.get("release"):
                        pool.release_pages()
                    poison_queue.update(ev.get("poison", ()))
            with (tr.phase("admit", tick=tick_no) if tr is not None
                  else _NO_PHASE):
                admit_arrivals()
                apply_cancels()
                expire_deadlines()
                admitted = 0
                if scheduler == "continuous":
                    budget = self.ecfg.max_prefill_per_tick
                    while (ready and budget > 0
                           and pool.can_admit(
                               self._effective_request(ready[0]))):
                        start(ready.popleft())
                        budget -= 1
                        admitted += 1
                else:  # static lockstep: full group in, nothing until out
                    if not active and ready:
                        while ready and pool.can_admit(
                                self._effective_request(ready[0])):
                            start(ready.popleft())
                            admitted += 1

                head_stuck = (ready and not admitted
                              and not pool.can_admit(
                                  self._effective_request(ready[0])))
                stall = stall + 1 if (head_stuck and active
                                      and scheduler == "continuous") else 0
                if (self._paged and active
                        and stall >= self.ecfg.preempt_after_ticks):
                    preempt_youngest()
                    stall = 0
                    continue  # retry admission before burning a tick

            if not active:
                if ready and not pending and not admitted:
                    # nothing running, nothing arriving, nothing admitted
                    # this pass, head-of-line refused: the pool can never
                    # satisfy it
                    if tr is not None:
                        tr.instant("admission_error", ENGINE_TRACK,
                                   rid=ready[0].request.rid)
                    raise AdmissionError(
                        ready[0].request.rid, pool.stats(),
                        queued=[s.request.rid for s in ready],
                        pages_needed=(
                            {s.request.rid:
                             pool.pages_needed(self._effective_request(s))
                             for s in ready} if self._paged else None))
                if pending:  # idle until the next arrival
                    with (tr.phase("idle", tick=tick_no) if tr is not None
                          else _NO_PHASE):
                        time.sleep(max(0.0, min(
                            pending[0].t_arrive - clock(), 0.005)))
                continue

            if self._paged:
                # decode-time page appends (prompt-only reservation):
                # back every active slot's write position before the
                # tick, oldest admission first.  Arena exhaustion here
                # routes through the existing preempt-youngest /
                # AdmissionError machinery — not a new failure mode.
                # A blocked slot is resolved IN PLACE (preempt until its
                # append lands) rather than by restarting the pass: the
                # freed pages would re-admit the preempted request first
                # and the blocked slot would never reach the tick below
                # (live-lock).
                with (tr.phase("page_append", tick=tick_no)
                      if tr is not None else _NO_PHASE):
                    for slot in sorted(active,
                                       key=lambda s: active[s].admit_seq):
                        while (slot in active and not pool.ensure_page(
                                slot, int(cur[slot]))):
                            if len(active) > 1:
                                preempt_youngest()  # may preempt `slot`
                                continue
                            st = active[slot]
                            if tr is not None:
                                tr.instant("admission_error", ENGINE_TRACK,
                                           rid=st.request.rid)
                            raise AdmissionError(
                                st.request.rid, pool.stats(),
                                queued=[s.request.rid for s in ready],
                                pages_needed={st.request.rid: 1})

            with (tr.phase("tick_prepare", tick=tick_no) if tr is not None
                  else _NO_PHASE):
                if poison_queue:
                    by_rid = {st.request.rid: slot
                              for slot, st in active.items()}
                    for rid in sorted(poison_queue):
                        if rid in by_rid:
                            poison_slot_cache(pool, by_rid[rid])
                            poison_queue.discard(rid)
                            if tr is not None:
                                tr.instant("poison", ("slot", by_rid[rid]),
                                           rid=rid)

                stochastic = bool(np.any(temps[list(active)] > 0))
                tick = self._tick_fn(stochastic, max_top_k, guard)
                # paged ticks take the block table as one more operand
                table = (jnp.asarray(pool.table),) if self._paged else ()
                operands = table + (
                    jnp.asarray(cur), jnp.asarray(last_tok[:, None]),
                    jnp.asarray(temps), jnp.asarray(topks),
                    jnp.asarray(rids), self._key)
                (metrics.tick_collectives,
                 metrics.tick_collective_bytes) = self._tick_collectives(
                     tick, (self.params, pool.cache) + operands)
            attempts = 0
            t_tick0 = clock() if tr is not None else 0.0
            t0 = time.perf_counter()
            with (tr.phase("tick_dispatch", tick=tick_no) if tr is not None
                  else _NO_PHASE):
                while True:
                    try:
                        if inj is not None and inj.take_failure(tick_no):
                            raise TickFailure(
                                f"injected tick failure at tick {tick_no}")
                        out, pool.cache = tick(self.params, pool.cache,
                                               *operands)
                        break
                    except TickFailure:
                        # transient device error: retry the identical
                        # tick (the injected raise precedes the call, so
                        # the donated cache was never consumed)
                        if attempts >= self.ecfg.max_retries:
                            raise
                        attempts += 1
                        metrics.retried += 1
                        if tr is not None:
                            tr.instant("tick_retry", ENGINE_TRACK,
                                       tick=tick_no, attempt=attempts)
                        time.sleep(self.ecfg.retry_backoff_s)
            with (tr.phase("tick_wait", tick=tick_no) if tr is not None
                  else _NO_PHASE):
                nxt = np.asarray(jax.block_until_ready(out))
            # guarded ticks encode a tripped slot as sentinel token -1
            valid = (nxt >= 0) if guard else None
            metrics.decode_time_s += time.perf_counter() - t0
            metrics.decode_ticks += 1
            metrics.occupancy_ticks += len(active)
            metrics.peak_active = max(metrics.peak_active, len(active))
            if tr is not None:
                t_now = clock()
                tr.span("tick", ENGINE_TRACK, t_tick0, t_now,
                        n_active=len(active))
                tr.counter("active_slots", len(active), t=t_now)
                tr.counter("ready_queue", len(ready), t=t_now)

            with (tr.phase("emit", tick=tick_no) if tr is not None
                  else _NO_PHASE):
                if valid is not None:
                    # quarantine: fail poisoned slots NOW — their garbage
                    # token is never appended, their (masked, soon to be
                    # recycled) cache rows free this tick
                    for slot in list(active):
                        if not valid[slot]:
                            if tr is not None:
                                tr.instant(
                                    "quarantine",
                                    ("req", active[slot].request.rid),
                                    slot=slot, where="decode")
                            evict(slot, FINISH_NUMERIC)
                            metrics.failed += 1
                metrics.decode_tokens += len(active)

                now = clock()
                for slot in list(active):
                    st = active[slot]
                    st.tokens.append(int(nxt[slot]))
                    metrics.itl_samples.append(now - st.t_last_token)
                    st.t_last_token = now
                    if st.done:
                        # Under 'static' the freed slot stays unused (and
                        # its lane keeps burning in every tick) until the
                        # whole group drains — admission is gated on
                        # `not active`.
                        evict(slot, None)
                    elif now > st.deadline_at:
                        evict(slot, FINISH_DEADLINE)
                        metrics.timed_out += 1
                    else:
                        cur[slot] = st.cur_index
                        last_tok[slot] = st.tokens[-1]

    def warmup(self, prompt_lens: Sequence[int], *,
               stochastic: bool = False) -> None:
        """Pre-compile prefill (per length) and the decode tick."""
        reqs = [
            Request(rid=-1000 - i, prompt=np.zeros(s, np.int32),
                    # a boundary prompt (s == s_max) only fits gen 1; its
                    # tick compiles via the other lengths or on first run
                    max_new_tokens=2 if s + 1 <= self.s_max else 1,
                    sampling=SamplingParams(
                        temperature=0.5 if stochastic else 0.0),
                    frames=(np.zeros((self.cfg.enc_seq, self.cfg.d_model),
                                     np.float32)
                            if self.cfg.family == "encdec" else None))
            for i, s in enumerate(prompt_lens)]
        self.run(reqs)


_SEQ_FNS: Dict[ArchConfig, tuple] = {}  # jit cache across reference calls


def generate_sequential(cfg: ArchConfig, params, request: Request, *,
                        top_k: int = 0,
                        s_max: Optional[int] = None,
                        seed: int = 0) -> GenerationResult:
    """Single-request reference: prefill + batch-1 decode loop.

    Uses the same model entry points, the same sampler and — for
    stochastic requests — the same (rid, position)-keyed PRNG streams as
    the engine (``seed`` must match ``EngineConfig.seed``), so an
    engine-vs-sequential mismatch isolates the serving machinery (cache
    pool, per-slot cur_index, recycling, tick composition) rather than
    sampler or kernel noise.

    Sampling knobs come from ``request.sampling``; the ``top_k`` kwarg
    is a deprecated fallback used only when the request carries none.
    ``sampling.deadline_ms`` is honored on a local wall clock from call
    start (the sequential twin of the engine's arrival clock): an
    expired request stops where it is — possibly with zero tokens —
    with ``finish_reason="deadline"``, so finish reasons stay
    comparable across the two paths.
    Returns a :class:`GenerationResult` (array-like: ``np.asarray`` of
    it is the token vector, as before).
    """
    policy = cfg.policy()
    s_max = s_max or cfg.max_seq
    if cfg not in _SEQ_FNS:
        _SEQ_FNS[cfg] = (jax.jit(make_prefill_step(cfg)),
                         jax.jit(make_decode_step(cfg), donate_argnums=(1,)))
    prefill, decode = _SEQ_FNS[cfg]

    sp = request.sampling
    temp = float(sp.temperature)
    k = sp.top_k or top_k
    base = jax.random.key(seed)
    t0 = time.perf_counter()
    deadline = (t0 + sp.deadline_ms / 1e3 if sp.deadline_ms is not None
                else float("inf"))

    def tok_key(pos: int):
        if temp == 0.0:
            return None
        return jax.random.fold_in(
            jax.random.fold_in(base, jnp.int32(request.rid)), jnp.int32(pos))

    from repro.serving.requests import (FINISH_DEADLINE, FINISH_LENGTH,
                                        FINISH_STOP)

    # real prefill -> first-token latency (was hardcoded 0.0, which made
    # sequential-vs-engine TTFT incomparable); stays 0.0 only when the
    # request expired before its first token existed
    ttft = [0.0]

    def result(out, reason):
        return GenerationResult(
            rid=request.rid, prompt_len=request.prompt_len,
            tokens=np.asarray(out, np.int32), ttft_s=ttft[0],
            finish_s=time.perf_counter() - t0, finish_reason=reason)

    if time.perf_counter() > deadline:
        return result([], FINISH_DEADLINE)
    logits, states, _ = prefill(params, prefill_batch(cfg, request))
    cache = SlotCachePool.grow(cfg, states, 1, s_max, jnp.dtype(cfg.dtype))
    out = [int(sample_tokens(logits[:, -1, :], policy=policy, top_k=k,
                             temperature=temp,
                             key=tok_key(request.prompt_len))[0])]
    ttft[0] = time.perf_counter() - t0
    stopped = out[-1] == sp.stop
    for i in range(request.max_new_tokens - 1):
        if stopped:
            break
        if time.perf_counter() > deadline:
            return result(out, FINISH_DEADLINE)
        cur = jnp.int32(request.prompt_len + i)
        step = {"token": jnp.asarray([[out[-1]]], jnp.int32)}
        if cfg.pos == "mrope":
            step["pos_ids"] = jnp.full((3, 1, 1), request.prompt_len + i,
                                       jnp.int32)
        lg, cache = decode(params, cache, cur, step)
        out.append(int(sample_tokens(
            lg[:, -1, :], policy=policy, top_k=k, temperature=temp,
            key=tok_key(request.prompt_len + i + 1))[0]))
        stopped = out[-1] == sp.stop
    # a request that completes is "length"/"stop" even if it also just
    # expired — same tie-break as the engine's post-tick check
    return result(out, FINISH_STOP if stopped else FINISH_LENGTH)
