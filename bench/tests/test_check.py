"""The sample that ``correct`` compares is spread over the slots."""

from types import SimpleNamespace

import numpy as np

from bench import check


def _out(prompt_len, n_tok, first, last):
    return SimpleNamespace(prompt_len=prompt_len, tokens=np.ones(n_tok),
                           ttft_s=first, finish_s=last)


def _outs():
    # rid 0 is the longest (live 0-40 s); rids 1-5 are live beside it
    # only at t=20 s, the busiest moment of its life; rids 6-29 are short
    # requests, one at a time, after it; rid 30 never got a token
    outs = {0: _out(2048, 512, 0.0, 40.0)}
    for r in range(1, 6):
        outs[r] = _out(512, 64, 19.0 - r, 20.0 + r)  # arrival 0
    for r in range(6, 30):
        outs[r] = _out(128, 16, 0.1, 0.5)
    outs[30] = _out(128, 0, 0.0, 0.0)
    arrivals = {r: 0.0 for r in range(6)}
    arrivals.update({r: 50.0 + r for r in range(6, 31)})
    return outs, arrivals


def test_sample_holds_the_longest_and_all_live_beside_it():
    outs, arrivals = _outs()
    for seed in (1, 2, 2 ** 31 + 3):
        picked = check.sample(outs, arrivals, seed, 8)
        assert picked[0] == 0
        assert set(picked[1:6]) == {1, 2, 3, 4, 5}
        assert len(picked) == len(set(picked)) == 8
        assert 30 not in picked


def test_sample_draws_the_rest_from_the_seed():
    outs, arrivals = _outs()
    a = check.sample(outs, arrivals, 1, 12)
    assert a == check.sample(outs, arrivals, 1, 12)
    assert a[6:] != check.sample(outs, arrivals, 2, 12)[6:]


def test_sample_stops_at_what_finished():
    outs, arrivals = _outs()
    assert sorted(check.sample(outs, arrivals, 1, 100)) == list(range(30))
    assert check.sample({30: outs[30]}, arrivals, 1, 4) == []
