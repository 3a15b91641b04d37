"""The serving loop's host spans and device names.

A tiny engine serves a few requests through ``AsyncServingFrontend``
under ``jax.profiler``; the trace is read back with
``jax.profiler.ProfileData`` and held to the engine's own counters and
``Request`` stamps. The device names (``lm_head``, ``sampler`` and the
Pallas kernels' names) are read from the lowered megastep's debug info.
"""
import asyncio
import dataclasses
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.launch.serve import AsyncServingFrontend
from repro.models import Model
from repro.quant import quantize_tree
from repro.serving import Request, ServingEngine

SPANS = ("frontend.admit", "frontend.cancel", "frontend.publish",
         "frontend.step", "engine.submit", "engine.stage", "engine.admit",
         "engine.dispatch", "engine.drain_wait", "engine.drain",
         "engine.first_token")
MEGASTEP = ("engine.stage", "engine.dispatch", "engine.drain_wait",
            "engine.drain")


def _cfg(kv="bf16"):
    cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=1)
    return dataclasses.replace(cfg, kv_quant=kv)


def _host_spans(trace_dir):
    """(name, start ns, end ns, stats) of the trace's program spans."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    stats = {k: v for k, v in e.stats}
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, stats))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Five requests of mixed lengths through two slots (so some wait
    in the queue), traced; returns (engine, requests, tokens streamed
    per uid, spans)."""
    cfg = _cfg()
    model = Model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                        max_len=64, megastep_k=4, admission="chunked",
                        prefill_chunk=8)
    reqs = []
    submit = eng.submit

    def recording_submit(req):
        reqs.append(req)
        submit(req)
    eng.submit = recording_submit
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (3, 11, 6, 17, 9)]
    streamed = {}

    async def drive():
        fe = AsyncServingFrontend(eng)

        async def one(i, p):
            toks = []
            out = await fe.generate(p, max_new_tokens=5 + i,
                                    temperature=0.0 if i % 2 else 0.7,
                                    on_token=toks.append)
            assert out == toks
            return toks
        outs = await asyncio.gather(*(one(i, p)
                                      for i, p in enumerate(prompts)))
        await fe.close()
        return outs

    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        outs = asyncio.run(drive())
    finally:
        jax.profiler.stop_trace()
    for req, toks in zip(reqs, outs):
        streamed[req.uid] = toks
    return eng, reqs, streamed, _host_spans(trace_dir)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_every_span_is_recorded(served):
    _, _, _, spans = served
    assert {s[0] for s in spans} == set(SPANS)


def test_megastep_spans_once_each_in_order(served):
    eng, _, _, spans = served
    n = eng.stats.megasteps
    assert n > 2
    seq = [s for s in spans if s[0] in MEGASTEP]
    assert [s[0] for s in seq] == list(MEGASTEP) * n
    for name in ("engine.dispatch", "engine.drain_wait"):
        assert [s[3]["megastep"] for s in _named(spans, name)] == \
            list(range(1, n + 1))
    # each engine step, hand-off to the executor thread included, lies
    # inside the front end's frontend.step span
    steps = _named(spans, "frontend.step")
    assert len(steps) >= n
    for i in range(n):
        first, last = seq[4 * i], seq[4 * i + 3]
        assert any(st[1] <= first[1] and last[2] <= st[2] for st in steps)


def test_request_uids_match_front_end_and_engine(served):
    _, reqs, streamed, spans = served
    uids = {r.uid for r in reqs}
    assert uids == set(streamed) == set(range(1, len(reqs) + 1))
    for name in ("engine.submit", "engine.admit", "engine.first_token"):
        got = [s[3]["uid"] for s in _named(spans, name)]
        assert sorted(got) == sorted(uids), name


def test_stats_agree_with_request_stamps(served):
    _, reqs, _, spans = served
    by_uid = {r.uid: r for r in reqs}
    for _, _, _, st in _named(spans, "engine.admit"):
        r = by_uid[st["uid"]]
        assert st["queued_us"] == pytest.approx(
            (r.t_admit - r._t_queued) * 1e6, rel=1e-9)
        assert r.t_submit <= r._t_queued <= r.t_admit
    waited = [s[3]["queued_us"] for s in _named(spans, "engine.admit")]
    assert max(waited) > 1e3      # two slots, five requests: some queue
    for _, _, _, st in _named(spans, "engine.first_token"):
        r = by_uid[st["uid"]]
        assert st["prefill_us"] == pytest.approx(
            (r.t_first_token - r.t_admit) * 1e6, rel=1e-9)
        assert st["ttft_us"] == pytest.approx(
            (r.t_first_token - r.t_submit) * 1e6, rel=1e-9)
        assert 0 < st["prefill_us"] <= st["ttft_us"]
    # a first token lands inside the drain that carried it
    drains = _named(spans, "engine.drain")
    for _, t0, t1, _ in _named(spans, "engine.first_token"):
        assert any(d[1] <= t0 and t1 <= d[2] for d in drains)


def test_counters_match_the_tokens_served(served):
    eng, reqs, streamed, spans = served
    prompt_tokens = sum(len(r.prompt) for r in reqs)
    assert eng.stats.prompt_tokens_fed == prompt_tokens
    assert sum(s[3]["fed"] for s in _named(spans, "engine.drain")) == \
        prompt_tokens
    out_tokens = sum(len(t) for t in streamed.values())
    assert out_tokens == eng.stats.tokens_generated
    assert sum(s[3]["emitted"] for s in _named(spans, "engine.drain")) == \
        out_tokens
    assert sum(s[3]["n"] for s in _named(spans, "frontend.publish")) == \
        out_tokens
    assert sum(s[3]["admitted"] for s in _named(spans, "engine.stage")) \
        == len(reqs)
    assert eng.stats.stage_wall_s > 0 and eng.stats.drain_wait_s > 0


def test_drain_counts_every_live_position_attended(served):
    """A request that ends holding ``n`` cache positions (its prompt and
    every output but the last) attended 1, 2, ..., n of them, one
    substep each."""
    _, reqs, streamed, spans = served
    held = [len(r.prompt) + len(streamed[r.uid]) - 1 for r in reqs]
    assert sum(s[3]["attended"] for s in _named(spans, "engine.drain")) \
        == sum(n * (n + 1) // 2 for n in held)


def test_prompt_tokens_fed_on_the_stall_path():
    cfg = _cfg()
    model = Model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                        max_len=64, megastep_k=4, admission="stall")
    reqs = [Request(uid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                    max_new_tokens=4) for i, n in enumerate((5, 9, 3))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.stats.prompt_tokens_fed == 17
    assert all(r.t_submit <= r.t_admit <= r.t_first_token for r in reqs)


@pytest.mark.parametrize("kv,attention", [("bf16", "decode_attention"),
                                          ("q8_0", "decode_attention_quant")])
@pytest.mark.parametrize("all_greedy", [False, True])
def test_megastep_hlo_names_scopes_and_kernels(kv, attention, all_greedy):
    cfg = dataclasses.replace(_cfg(kv), quant_policy="q8_0")
    model = Model(cfg)
    params = quantize_tree(model.init(jax.random.PRNGKey(0)), "q8_0",
                           cfg.quant_group)
    eng = ServingEngine(model, params, slots=2, max_len=64, megastep_k=2,
                        kernels="pallas", quant_policy="q8_0", kv_quant=kv)
    text = eng._megastep.lower(eng.params, eng.cache, eng.state,
                               eng._empty_admit(), all_greedy
                               ).as_text(debug_info=True)
    # a scope or kernel name heads a location's name stack, or sits in it
    for name in ("lm_head", "sampler", "quant_matmul", attention):
        assert re.search(rf'loc\("([^"]*/)?{name}[/"]', text), name
