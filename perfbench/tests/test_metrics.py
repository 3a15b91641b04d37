"""The metric arithmetic of the readers: a rate over the whole window,
a tail over all requests, censored time to first token, and the window
client's timing from the due time with a stall injected."""
import asyncio
import time
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import spec, window
from perfbench.generator import RequestSpec


def _rec(index, due, times, in_window=True, failed=False, greedy=True):
    s = RequestSpec(index=index, prompt=np.ones(4, np.int32), max_new=8,
                    temperature=0.0 if greedy else 0.8, top_k=0,
                    due_s=due, in_window=in_window)
    r = window.Record(spec=s, due=due, sent=due, times=list(times),
                      tokens=[1] * len(times), done=not failed,
                      failed=failed)
    return r


def _obs(records, steps=(), t0=0.0, t1=10.0, loop="open", slots=4):
    return SimpleNamespace(records=records, steps=list(steps), t0=t0, t1=t1,
                           mix={"loop": loop}, slots=slots, trace=None,
                           setup_s=12.5)


def read(name, obs):
    return spec.reader(name).read(obs)


def test_rate_over_the_whole_window():
    recs = [_rec(0, -1.0, [-0.5, 0.5, 1.0]),     # one token before t0
            _rec(1, 2.0, [3.0, 9.5, 10.5])]      # one after t1
    assert read("tokens_per_s", _obs(recs)) == pytest.approx(4 / 10.0)


def test_ttft_tail_over_all_requests_with_censoring():
    recs = [_rec(i, float(i), [i + 0.1 * (i + 1)]) for i in range(8)]
    recs.append(_rec(8, 9.0, []))                # no first token: 10 - 9
    recs.append(_rec(9, 9.5, [11.0]))            # first token after t1
    recs.append(_rec(10, -2.0, [5.0], in_window=False))   # lead-in: out
    recs.append(_rec(11, 3.0, [3.05], failed=True))       # failed: 10 - 3
    vals = [0.1 * (i + 1) for i in range(8)] + [1.0, 0.5, 7.0]
    got = read("ttft_p90_ms", _obs(recs))
    assert got == pytest.approx(1e3 * np.percentile(vals, 90))
    assert read("ttft_p90_ms", _obs(recs, loop="closed")) is None


def test_itl_gaps_ending_in_the_window():
    recs = [_rec(0, 0.0, [-1.0, 0.5, 0.5, 2.5]),
            _rec(1, 0.0, [9.0, 10.0, 12.0])]
    gaps = [1.5, 0.0, 2.0]                      # 10.0 and 12.0 end outside
    assert read("itl_p95_ms", _obs(recs)) == pytest.approx(
        1e3 * np.percentile(gaps, 95))


def test_engine_counters():
    S = window.Step
    steps = [S(0.0, 1.0, 8, 10, 12, 50.0, True),
             S(1.5, 2.5, 8, 0, 8, 60.0, True),
             S(2.5, 3.0, 8, 0, 1, 5.0, False),
             S(4.0, 5.0, 8, 0, 1, 5.0, True),
             S(20.0, 21.0, 8, 0, 8, 9.0, True)]   # after the window
    obs = _obs([], steps)
    cap = 4 * 8 * 4
    assert read("slot_busy_share", obs) == pytest.approx(100 * 22 / cap)
    assert read("prefill_slot_share", obs) == pytest.approx(100 * 10 / cap)
    # gaps while work was pending: 0.5 after step 0, 0 after step 1; the
    # gap after step 2 (nothing pending) does not count
    assert read("frontend_gap_share", obs) == pytest.approx(100 * 0.5 / 10)
    assert read("setup_s", obs) == 12.5
    for name in ("megastep_ms", "megastep_mfu", "quant_matmul_roofline",
                 "decode_attention_roofline", "device_idle_share"):
        assert read(name, obs) is None          # nothing traced to read


class _StallFrontEnd:
    """Streams each request's tokens 10 ms after it is sent; the first
    call blocks the event loop for ``stall`` seconds, as a stalled host
    would."""

    def __init__(self, stall):
        self.stall = stall

    async def generate(self, prompt, *, max_new_tokens, temperature, top_k,
                       on_token):
        if self.stall:
            time.sleep(self.stall)
            self.stall = 0.0
        await asyncio.sleep(0.01)
        for _ in range(max_new_tokens):
            on_token(1)

    async def close(self):
        pass


def test_open_loop_times_from_the_due_time_through_a_stall():
    specs = [RequestSpec(i, np.ones(2, np.int32), 2, 0.0, 0, 0.05 * i, True)
             for i in range(6)]
    recs, t0, t1 = asyncio.run(window.run_open(
        _StallFrontEnd(0.3), specs, 0.5, 0.0, lambda: None, lambda: None))
    ttft = [r.times[0] - r.due for r in recs]
    # requests due during the stall were sent late: their wait counts
    assert recs[1].sent - recs[1].due > 0.2
    assert ttft[1] > 0.2 and ttft[5] < 0.1
    obs = _obs(recs, t0=t0, t1=t1)
    assert read("ttft_p90_ms", obs) > 200.0


class _SlowPromptFrontEnd:
    """Takes ``prompt_s`` before the first token, then streams one token
    every 10 ms."""

    def __init__(self, prompt_s):
        self.prompt_s = prompt_s

    async def generate(self, prompt, *, max_new_tokens, temperature, top_k,
                       on_token):
        await asyncio.sleep(self.prompt_s)
        for _ in range(max_new_tokens):
            on_token(1)
            await asyncio.sleep(0.01)

    async def close(self):
        pass


@pytest.mark.parametrize("at_first_token", [False, True])
def test_closed_loop_window_opens_at_the_first_token(at_first_token):
    specs = [RequestSpec(0, np.ones(2, np.int32), 1000, 0.0, 0, None, False)]
    recs, t0, t1 = asyncio.run(window.run_closed(
        _SlowPromptFrontEnd(0.2), specs, 0.3, 1, lambda: None,
        lambda: None, at_first_token))
    assert len(recs) == 1 and t1 - t0 == pytest.approx(0.3)
    first = recs[0].times[0]
    if at_first_token:
        # the prompt is before the window; only decode lies inside it
        assert first < t0 < recs[0].times[1]
        assert read("tokens_per_s", _obs(recs, t0=t0, t1=t1)) > 50
    else:
        assert first > t0 + 0.15          # the prompt lies inside it
    assert recs[0].tokens and not recs[0].done     # in flight at the end
