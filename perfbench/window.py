"""The measured window: a client that drives the program's front end.

Each request goes through ``AsyncServingFrontend.generate`` with an
``on_token`` callback that stamps every streamed token on the host
clock. An open loop sends each request at its due time, whether or not
earlier ones have finished, and the request is timed from that due
time; a closed loop's clients send the next request when the last one
completes. When the window closes, requests still in flight are
cancelled; their streamed tokens are checked with the finished ones.

``StepProbe`` wraps ``engine.step`` (the call from the front end into
the engine layer): it records each call's span on the host clock, the
substeps it ran (``EngineStats.steps``), and the prompt tokens fed,
tokens run and cache positions attended, from the engine's host view
of its slots. With ``trace`` on,
the span is also written into the profiler's trace as ``engine.step``.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax

from perfbench.generator import RequestSpec

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    spec: RequestSpec
    due: float = 0.0                 # host clock: when it was due
    sent: float = 0.0                # host clock: when it was sent
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    failed: bool = False
    error: Optional[str] = None


@dataclasses.dataclass
class Step:
    start: float
    end: float
    substeps: int        # decode substeps the megastep ran (K)
    fed: int             # prompt tokens fed through the model
    advanced: int        # tokens run through the model (cache positions
                         # written): prompt tokens and fed-back outputs
    attended: float      # live cache positions read, summed over substeps
    pending: bool        # work left in the engine after the call


def _cache_len(engine, s, req) -> int:
    """Positions ``req`` holds in its slot's cache, from the host view:
    the prompt fed so far, then the prompt and every emitted token but
    the last (which is fed on the next substep)."""
    if req.output:
        return len(req.prompt) + len(req.output) - 1
    if s is not None and engine.active[s] is req:
        return int(engine._prefill_pos[s])
    return 0


def _fed(req, cache_len: int) -> int:
    return min(cache_len, len(req.prompt))


class StepProbe:
    def __init__(self, engine, trace: bool):
        self.engine = engine
        self.steps: List[Step] = []
        self._orig = engine.step
        self._trace = trace
        engine.step = self.step

    def step(self):
        e = self.engine
        before: Dict[int, tuple] = {}
        for s, r in enumerate(e.active):
            if r is not None:
                before[id(r)] = (r, _cache_len(e, s, r))
        sub0 = e.stats.steps
        t0 = clock()
        ctx = (jax.profiler.TraceAnnotation("engine.step") if self._trace
               else contextlib.nullcontext())
        with ctx:
            n = self._orig()
        t1 = clock()
        after = {id(r): (r, s) for s, r in enumerate(e.active)
                 if r is not None}
        fed = advanced = 0
        attended = 0.0
        for key in set(before) | set(after):
            r, b = before.get(key, (after.get(key, (None,))[0], 0))
            a = _cache_len(e, after[key][1] if key in after else None, r)
            if key not in after and not r.output:
                a = b                     # cancelled mid-prompt
            adv = max(a - b, 0)
            advanced += adv
            fed += _fed(r, a) - _fed(r, b)
            attended += adv * b + adv * (adv + 1) / 2.0
        self.steps.append(Step(t0, t1, e.stats.steps - sub0, fed, advanced,
                               attended, e.has_work()))
        return n


async def _one(fe, rec: Record,
               first: Optional[asyncio.Event] = None) -> None:
    def on_token(tok):
        rec.times.append(clock())
        rec.tokens.append(int(tok))
        if first is not None:
            first.set()
    rec.sent = clock()
    try:
        await fe.generate(rec.spec.prompt,
                          max_new_tokens=rec.spec.max_new,
                          temperature=rec.spec.temperature,
                          top_k=rec.spec.top_k, on_token=on_token)
        rec.done = True
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is counted
        rec.failed = True
        rec.error = f"{type(e).__name__}: {e}"


async def _sleep_until(t: float) -> None:
    dt = t - clock()
    if dt > 0:
        await asyncio.sleep(dt)


async def _close(fe, tasks) -> None:
    for t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await fe.close()


async def run_open(fe, specs: List[RequestSpec], seconds: float,
                   lead_in: float, on_start, on_end):
    """Send ``specs`` at their due times; returns (records, window
    start, window end). ``on_start`` and ``on_end`` run as the window
    opens and closes."""
    start = clock() + lead_in
    recs, tasks = [], []
    started = False
    for spec in specs:
        if spec.in_window and not started:
            await _sleep_until(start)
            on_start()
            started = True
        rec = Record(spec=spec, due=start + spec.due_s)
        await _sleep_until(rec.due)
        recs.append(rec)
        tasks.append(asyncio.ensure_future(_one(fe, rec)))
    await _sleep_until(start + seconds)
    on_end()
    await _close(fe, tasks)
    return recs, start, start + seconds


async def run_closed(fe, specs: List[RequestSpec], seconds: float,
                     clients: int, on_start, on_end,
                     at_first_token: bool = False):
    """``clients`` users, each sending the next spec (in turn, cycling)
    when its last request completes; returns (records, start, end).
    With ``at_first_token`` the window opens once the first token has
    streamed, so it holds no prompt of the first requests, only what
    follows them."""
    first = asyncio.Event()
    window = {"end": float("inf")}
    recs: List[Record] = []
    nxt = iter(range(1 << 30))

    async def client():
        while clock() < window["end"]:
            spec = specs[next(nxt) % len(specs)]
            rec = Record(spec=spec, due=clock())
            recs.append(rec)
            await _one(fe, rec, first)

    if not at_first_token:
        first.set()
    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    await first.wait()
    on_start()
    start = clock()
    end = window["end"] = start + seconds
    await _sleep_until(end)
    on_end()
    await _close(fe, tasks)
    return recs, start, end
