"""The traffic generator: the same seed gives the same requests, every
seed the same multiset of sizes and gaps, and a mix is data only."""
import numpy as np
import pytest

from perfbench import generator, spec

BIG = 2 ** 33 + 12345          # seeds may need more than 32 bits


def _sizes(reqs):
    """Sizes of the window's requests (an open loop's lead-in repeats
    the window's tail, which depends on the order)."""
    win = [r for r in reqs if r.in_window or r.due_s is None]
    return (sorted(len(r.prompt) for r in win),
            sorted(r.max_new for r in win))


@pytest.mark.parametrize("mix_name", ["chat", "solo"])
def test_same_seed_same_requests(mix_name):
    mix = spec.load_mix(mix_name)
    a = generator.schedule(mix, BIG, 30.0, 1000)
    b = generator.schedule(mix, BIG, 30.0, 1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.temperature, x.top_k, x.due_s) == \
            (y.max_new, y.temperature, y.top_k, y.due_s)


@pytest.mark.parametrize("mix_name", ["chat", "solo"])
def test_every_seed_draws_the_same_work(mix_name):
    mix = spec.load_mix(mix_name)
    a = generator.schedule(mix, 1, 30.0, 1000)
    b = generator.schedule(mix, BIG, 30.0, 1000)
    assert _sizes(a) == _sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sum(r.greedy for r in a if r.in_window) == \
        sum(r.greedy for r in b if r.in_window)
    assert any(not np.array_equal(x.prompt[:4], y.prompt[:4])
               for x, y in zip(a, b))


def test_open_loop_fills_the_window():
    mix = dict(spec.load_mix("chat"), rate_per_s=2.0, lead_in_s=4)
    reqs = generator.schedule(mix, 5, 30.0, 1000)
    win = [r for r in reqs if r.in_window]
    lead = [r for r in reqs if not r.in_window]
    assert len(win) == 60 and 4 <= len(lead) <= 12
    assert all(-4.0 <= r.due_s < 0 for r in lead)
    assert [r.index for r in reqs] == list(range(len(reqs)))
    # the lead-in repeats the window's tail a window earlier, new tokens
    tail = win[-len(lead):]
    assert [(len(a.prompt), a.max_new, a.greedy, a.due_s + 30.0)
            for a in lead] == [(len(b.prompt), b.max_new, b.greedy, b.due_s)
                               for b in tail]
    assert not np.array_equal(lead[0].prompt, tail[0].prompt)
    dues = [r.due_s for r in win]
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 30.0
    assert sum(r.greedy for r in win) in (14, 15, 16)   # a quarter
    p = mix["prompt_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(1 <= t < 1000 for r in reqs for t in r.prompt)


def test_quantiles_and_order():
    u = generator.stratified(8, 2, 0)
    assert sorted(u) == [(j + 0.5) / 8 for j in range(8)]
    # van der Corput order: any 4 consecutive levels cover both halves
    for i in range(5):
        w = u[i:i + 4]
        assert (w < 0.5).any() and (w > 0.5).any()
    d = {"dist": "uniform", "min": 3, "max": 6}
    assert list(generator.quantile(d, np.array([0.01, 0.3, 0.6, 0.99]))) \
        == [3, 4, 5, 6]
    ln = {"dist": "lognormal", "median": 100, "sigma": 0.6, "min": 1,
          "max": 10 ** 6}
    assert generator.quantile(ln, np.array([0.5]))[0] == 100
