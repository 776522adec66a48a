import json

import numpy as np
import pytest

from bench import traffic_gen as tg
from conftest import ROOT

CHAT = json.loads((ROOT / "bench/traffic/chat.json").read_text())
LONG = json.loads((ROOT / "bench/traffic/long_prompt.json").read_text())
MIXES = {p.stem: json.loads(p.read_text())
         for p in sorted((ROOT / "bench/traffic").glob("*.json"))}
BIG_SEED = 2 ** 31 + 12345


def _sizes(items):
    return sorted((it.prompt_len, it.max_new) for it in items)


@pytest.mark.parametrize("mix", list(MIXES.values()), ids=list(MIXES))
def test_same_seed_same_schedule_and_tokens(mix):
    a = tg.schedule(mix, BIG_SEED, 51)
    b = tg.schedule(mix, BIG_SEED, 51)
    assert a == b
    ta = tg.prompt_tokens(a, BIG_SEED, 92544)
    tb = tg.prompt_tokens(b, BIG_SEED, 92544)
    assert all(np.array_equal(x, y) for x, y in zip(ta, tb))
    assert all(x.dtype == np.int32 and x.min() >= 0 and x.max() < 92544
               for x in ta)


@pytest.mark.parametrize("mix", list(MIXES.values()), ids=list(MIXES))
def test_other_seed_same_work_other_order(mix):
    a = tg.schedule(mix, 1, 51)
    b = tg.schedule(mix, BIG_SEED, 51)
    assert len(a) == len(b)
    assert [(i.prompt_len, i.max_new) for i in a] != \
        [(i.prompt_len, i.max_new) for i in b]
    if mix["arrivals"]["kind"] == "backlog":
        # the queue's head holds the same work: rounds are permutations
        per = mix["arrivals"]["round"]
        for r in range(len(a) // per):
            assert _sizes(a[r * per:(r + 1) * per]) == \
                _sizes(b[r * per:(r + 1) * per])
    else:
        assert _sizes(a) == _sizes(b)
        # the gap after the last arrival closes the window
        ga = sorted(np.diff([i.arrival_s for i in a] + [51.0]))
        gb = sorted(np.diff([i.arrival_s for i in b] + [51.0]))
        np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)
    assert tg.prompt_tokens(a, 1, 100)[0].tolist() != \
        tg.prompt_tokens(a, BIG_SEED, 100)[0].tolist()


def test_chat_is_open_loop_poisson_on_the_grid():
    items = tg.schedule(CHAT, 7, 51)
    rate = CHAT["arrivals"]["rate_per_s"]
    assert len(items) == round(rate * 51)
    times = [i.arrival_s for i in items]
    assert times == sorted(times) and times[0] == 0.0 and times[-1] < 51
    assert {i.prompt_len for i in items} <= set(CHAT["prompt"]["grid"])
    plens = sorted(i.prompt_len for i in items)
    assert plens[len(plens) // 2] == CHAT["prompt"]["median"]
    outs = sorted(i.max_new for i in items)
    assert CHAT["output"]["min"] <= outs[0] and outs[-1] <= CHAT["output"]["max"]
    assert abs(outs[len(outs) // 2] - CHAT["output"]["median"]) <= 2
    # heavy tail: the longest outputs are several times the median
    assert outs[-1] >= 3 * CHAT["output"]["median"]
    # exponential gaps: coefficient of variation near 1
    gaps = np.diff(times)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


@pytest.mark.parametrize("mix", list(MIXES.values()), ids=list(MIXES))
def test_every_mix_names_its_source(mix):
    assert mix["source"].strip() and mix["arrivals"]["kind"] in tg.ARRIVAL_KINDS
    assert mix["check"]["requests"] >= mix["engine"]["n_slots"]


def test_backlog_is_due_at_zero_on_the_grid():
    items = tg.schedule(LONG, 7, 51)
    assert len(items) == LONG["arrivals"]["n_requests"]
    assert all(i.arrival_s == 0.0 for i in items)
    assert {i.prompt_len for i in items} == set(LONG["prompt"]["grid"])
    assert all(LONG["output"]["min"] <= i.max_new <= LONG["output"]["max"]
               for i in items)


@pytest.mark.parametrize("mix", list(MIXES.values()), ids=list(MIXES))
def test_warmed_lengths_are_what_every_seed_sends(mix):
    used = tg.used_prompt_lengths(mix, 51)
    assert set(used) <= set(mix["prompt"]["grid"])
    for seed in (0, 3, BIG_SEED):
        assert {i.prompt_len for i in tg.schedule(mix, seed, 51)} == set(used)
