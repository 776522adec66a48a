"""The one traffic generator: a mix file's parameters and a seed -> a schedule.

A traffic mix (``bench/traffic/<mix>.json``) is data only:

    {"source": "<the public trace or benchmark its lengths come from>",
     "arrivals": {"kind": "poisson", "rate_per_s": 2.4},
     "prompt": {"grid": [128, 256, 512], "median": 256, "sigma": 0.5},
     "output": {"min": 16, "max": 512, "median": 128, "sigma": 0.8},
     "drain_s": 4.0, ...}

Arrival kinds:

* ``poisson`` -- open loop at ``rate_per_s`` over the window;
* ``backlog`` -- ``n_requests`` all due at t=0.

Every seed gets the same work: the request count, the multiset of prompt
and output lengths and the multiset of inter-arrival gaps are fixed by
the mix and the window length, and the seed only orders them (and draws
the prompt tokens).  Lengths are log-normal quantiles: prompts snap to
the nearest ``grid`` value in log space (the program compiles one
prefill per prompt length, and set-up warms the lengths a run sends); outputs
are clipped to ``[min, max]``.  For ``backlog`` the lengths come in
rounds, each round one shuffled copy of the quantile set, so that the
part of the queue a window reaches holds nearly the same work for
every seed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

ARRIVAL_KINDS = ("poisson", "backlog")


@dataclass(frozen=True)
class Item:
    """One scheduled request: due time (s from window start) and sizes."""

    rid: int
    arrival_s: float
    prompt_len: int
    max_new: int


def _quantiles(n: int) -> List[float]:
    """The n standard-normal quantiles at (i + 1/2) / n."""
    nd = NormalDist()
    return [nd.inv_cdf((i + 0.5) / n) for i in range(n)]


def prompt_lengths(spec: dict, n: int) -> List[int]:
    grid = sorted(spec["grid"])
    logs = [math.log(g) for g in grid]
    out = []
    for z in _quantiles(n):
        x = math.log(spec["median"]) + spec["sigma"] * z
        out.append(grid[min(range(len(grid)), key=lambda i: abs(logs[i] - x))])
    return out


def output_lengths(spec: dict, n: int) -> List[int]:
    return [int(min(spec["max"], max(spec["min"], round(
        math.exp(math.log(spec["median"]) + spec["sigma"] * z)))))
        for z in _quantiles(n)]


def _gaps(rate: float, n: int) -> List[float]:
    """n exponential inter-arrival quantiles of mean 1/rate."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def _seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def schedule(mix: dict, seed: int, seconds: float) -> List[Item]:
    """The whole arrival schedule of one run, before the window opens."""
    arr = mix["arrivals"]
    kind = arr["kind"]
    rng = _seed_rng(seed, 1)
    if kind == "backlog":
        n = int(arr["n_requests"])
        per = int(arr.get("round", n))
        pl = prompt_lengths(mix["prompt"], per)
        ol = output_lengths(mix["output"], per)
        pairs = []
        while len(pairs) < n:
            rnd = list(zip(pl, ol))
            rng.shuffle(rnd)
            pairs.extend(rnd)
        return [Item(i, 0.0, p, o) for i, (p, o) in enumerate(pairs[:n])]
    if kind != "poisson":
        raise ValueError(f"unknown arrival kind {kind!r}; "
                         f"known: {ARRIVAL_KINDS}")
    rate = float(arr["rate_per_s"])
    n = int(round(rate * seconds))
    gaps = _gaps(rate, n)
    rng.shuffle(gaps)
    # stretch the fixed gap set to fill the window exactly, so the count
    # and the offered load do not depend on the order
    scale = seconds / sum(gaps) if n else 1.0
    times: List[float] = []
    t = 0.0
    for g in gaps:
        times.append(t)
        t += g * scale
    pl = prompt_lengths(mix["prompt"], n)
    ol = output_lengths(mix["output"], n)
    order = rng.permutation(n)
    return [Item(i, times[i], pl[j], ol[j]) for i, j in enumerate(order)]


def prompt_tokens(items: List[Item], seed: int, vocab: int) -> List[np.ndarray]:
    """Random prompt token ids, one array per item, from the seed."""
    rng = _seed_rng(seed, 2)
    return [rng.integers(0, vocab, it.prompt_len, dtype=np.int32)
            for it in items]


def used_prompt_lengths(mix: dict, seconds: float) -> List[int]:
    """The prompt lengths a run of this length sends, whatever its seed
    (what set-up warms, and nothing else)."""
    return sorted({it.prompt_len for it in schedule(mix, 0, seconds)})
