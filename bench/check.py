"""Decides ``correct``: what the window served against the plain reference.

Once the window has closed, a sample of the finished requests is run
through the float32 reference (``bench/reference/dense_gqa.py``) over
its prompt and every token the program served, with the arithmetic the
configuration file states (its ``rms_norm_eps``, ``rope_theta`` and any
of Granite's multipliers, scaling and tied head), not the program's.
The sample is spread over the slots: it holds the longest request,
every request that was being served beside it at the busiest moment of
its life (requests live at one moment sit in different slots), and then
others drawn from the seed, up to the mix's ``check.requests``.  For
each served (greedy) token the gap is the reference's best logit at that
position minus the reference's logit of the served token: 0 where the
two agree, and small where a near tie fell the other way under the
program's rounding.  The widest gap of the sample is compared with the
cell's limit (``bench/limits/<cell>.json``), which was set between the
program's readings over a dozen seeds and those of its int8 path (the
control).

A request the engine failed with ``numeric_error`` also makes the run
incorrect: it served something that is not a token.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.reference import dense_gqa
from repro.serving.requests import (FINISH_DEADLINE, FINISH_NUMERIC,
                                    FINISH_REJECTED)


def is_failure(cell, out) -> bool:
    """Whether a request counts as failed: refused or broken, or (where
    the mix says so) expired with no token at all."""
    if out.finish_reason in (FINISH_NUMERIC, FINISH_REJECTED):
        return True
    return (out.finish_reason == FINISH_DEADLINE and len(out.tokens) == 0
            and bool(cell.mix.get("expiry_is_failure", True)))


def _live_spans(outs, arrivals: Dict[int, float], rids):
    """Each request's time in its slot: first token to last token."""
    return {r: (arrivals[r] + outs[r].ttft_s, arrivals[r] + outs[r].finish_s)
            for r in rids}


def sample(outs, arrivals: Dict[int, float], seed: int,
           n_requests: int) -> List[int]:
    """Rids to compare: the longest finished request, every request live
    beside it at the moment of its life when most were live (each in a
    slot of its own), then others in an order drawn from the seed until
    ``n_requests`` are in."""
    done = sorted(rid for rid, o in outs.items() if len(o.tokens) > 0)
    if not done:
        return []
    longest = max(done, key=lambda r: (outs[r].prompt_len
                                       + len(outs[r].tokens), -r))
    span = _live_spans(outs, arrivals, done)
    lo, hi = span[longest]
    moments = [lo] + [span[r][0] for r in done if lo < span[r][0] <= hi]

    def live(t):
        return [r for r in done
                if r != longest and span[r][0] <= t <= span[r][1]]

    beside = max((live(t) for t in moments), key=len)
    picked = [longest] + beside
    rest = [r for r in done if r not in set(picked)]
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    for i in rng.permutation(len(rest)):
        if len(picked) >= n_requests:
            break
        picked.append(rest[i])
    return picked


def reference_gaps(params, config: dict, mix: dict,
                   prompts: Dict[int, np.ndarray], outs,
                   rids: List[int]) -> Dict[int, np.ndarray]:
    """Gap of every served token of ``rids``, per request, by the
    reference with the arithmetic of the configuration file ``config``."""
    arch = dense_gqa.arithmetic(config)
    n_out = int(mix["output"]["max"])
    s_pad = dense_gqa.padded_len(max(mix["prompt"]["grid"]) + n_out)
    gaps = {}
    for rid in rids:
        served = np.asarray(outs[rid].tokens, np.int32)
        seq = np.concatenate([prompts[rid], served[:-1]])
        seq = np.pad(seq, (0, s_pad - len(seq)))
        gaps[rid] = dense_gqa.served_gaps(
            params, seq, len(prompts[rid]), served, n_out=n_out, arch=arch)
    return gaps


def compare(params, cell, requests, outs, seed: int) -> dict:
    """The verdict and each compared number beside its limit."""
    prompts = {r.rid: r.prompt for r in requests}
    arrivals = {r.rid: float(r.arrival_time) for r in requests}
    rids = sample(outs, arrivals, seed, int(cell.mix["check"]["requests"]))
    gaps = reference_gaps(params, cell.config, cell.mix, prompts, outs, rids)
    # no finished request leaves nothing to compare: not correct
    widest = max((float(g.max()) for g in gaps.values()), default=None)
    n_numeric = sum(1 for o in outs.values()
                    if o.finish_reason == FINISH_NUMERIC)
    limit = float(cell.limits["max_logit_gap"])
    checks = {
        "max_logit_gap": {"value": widest, "limit": limit,
                          "tokens": int(sum(len(g) for g in gaps.values())),
                          "requests": len(gaps)},
        "numeric_errors": {"value": n_numeric, "limit": 0},
    }
    correct = bool(widest is not None and widest <= limit
                   and n_numeric == 0)
    return {"correct": correct, "checks": checks, "gaps": gaps}
