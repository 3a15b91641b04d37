"""End-to-end serving benchmark: the ServingEngine decoding batched
requests on a reduced model (live execution).

Eight sweeps (``--sweep
megastep|mixed|precision|kv|kernels|async|paging|overload|all``):

1. **Megastep sweep** — ``K ∈ {1, 4, 8, 16}``, all requests queued
   upfront (stall admission, the PR-1 configuration): K=1 reproduces
   the per-token-dispatch configuration the paper's §5 measures losing
   on the Apple GPU; larger K amortizes the host dispatch over one
   fused ``lax.scan``.
2. **Mixed-workload sweep** — a seeded Poisson-ish arrival trace of
   short-prompt requests lands *while the batch decodes*, replayed
   identically against stall-prefill admission (each arrival wave
   pays a batched-prefill dispatch that stalls every decoding slot)
   and chunked-prefill admission (prompts ride inside the megastep
   scan; zero extra dispatches). This is the regime where the
   sustained-load studies (arXiv:2410.03613) put the on-device
   collapse — and where the dispatch-overhead lesson says chunked
   admission must win decode-phase tokens/s.
3. **Precision sweep** — {bf16, q8_0, q4_0} × K ∈ {1, 8} serving
   decode, the paper's §5.3 quantization table reproduced through the
   megastep engine. The JSON's ``precision`` section is the live
   counterpart of the paper's F16/Q8_0/Q4_0 throughput columns: per
   (format, K) decode-phase tok/s, the q4_0/bf16 ratio at K=8, the
   measured weight-bytes ratio (paper fn.1: Q4_0 = 4.5 bits/weight),
   and the analytic prediction from
   ``core.scheduler.simulate_precision`` (the memory-roofline §5.3
   model) next to the measurement — when the backend's dequant path
   inverts the predicted ordering, that gap is the recorded finding
   (see ROADMAP.md).
4. **KV-precision sweep** — {bf16, q8_0, q4_0} *cache* × K ∈ {1, 8}
   at a long-context operating point: the cache is the decode stream
   that grows with context/batch, so this is where the paper's
   CPU-vs-GPU crossover math lives at long context. The JSON's
   ``kv_precision`` section reports decode tok/s per (format, K), the
   measured cache-bytes ratio (must come out ≈ bits/16: int8 payload +
   groupwise scales), and ``simulate_kv_precision``'s prediction at
   toy and paper-scale context.
5. **Kernel-backend sweep** — {q8_0, q4_0} weights+cache ×
   {xla, pallas} through the engine: greedy token-identity across
   backends (the fused-dequant kernel contract) plus the analytic
   TPU-v5e planner flip (xla prices the materialized q4 unpack and
   picks q8_0; the fused pallas backend hands the win back to q4_0).
   Emitted as the JSON's ``kernel_backend`` section.

6. **Async-overlap sweep** — ``pipeline_depth ∈ {1, 2, 4}`` on the
   same engine (the knob is pure host orchestration; the compiled
   megastep is shared) at **K=1**, the paper's per-token-dispatch
   regime: with one decode token per dispatch the host's per-megastep
   work — dispatch-call overhead, draining the packed ``(tokens,
   emitted, pos)`` block, staging the next admission — is comparable
   to the device step, so hiding it behind in-flight megasteps is
   exactly the §5 launch-overhead story attacked from the other side
   (pipelining instead of amortization). The measured gap is
   ``(decode_wall - drain_wait) / megasteps``: host-side work that
   extends the serving period beyond the device wait. It shrinks at
   depth > 1 because part of the dispatch/drain runs while the device
   executes the previous in-flight megastep. Two measured caveats are
   recorded rather than hidden: (a) carry *donation* serializes the
   dispatch chain on this backend (donating a buffer that is itself a
   pending computation's output blocks the call until it
   materializes), so the sweep runs ``donate_carries=False`` — the
   donation-vs-overlap tradeoff is real and the section says so; (b)
   at K >= 2 the device step dwarfs the host gap and the stale slot
   view's wasted trailing substeps eat the overlap win — amortization
   and pipelining attack the same gap, and once K has amortized it
   there is nothing left to hide. Greedy token-identity across depths
   is asserted (pipelining must move time, never tokens), and
   ``simulate_async_overlap`` provides the analytic prediction.
   Emitted as the JSON's ``async_overlap`` section.

7. **Paging sweep** — dense per-slot cache vs the paged pool
   (``page_size ∈ {8, 16, 32}``) through the engine: greedy
   token-identity (paging moves bytes, never tokens), decode tok/s
   (the gather-indirection tax, a pure cost at prefix hit rate 0),
   and the tentpole claim — *cache bytes scale with live tokens*:
   the dense engine preallocates ``slots x max_len`` rows while the
   paged pool's peak in-use blocks track the workload's live token
   count across increasing loads. A prefix-cache leg serves a
   shared-system-prompt workload (the Xiao et al. mobile traffic
   shape) and records hit/hit-token counters plus the admission
   substeps the copy-on-write mapping saves.
   ``simulate_paging`` provides the analytic twin. Emitted as the
   JSON's ``paging`` section.

8. **Overload sweep** — seeded Poisson arrivals at {1, 2, 3}x the
   engine's *measured* capacity, replayed tick-identically against
   two admission policies on the same compiled engine: **bounded**
   (``max_queue = 2 x slots`` + per-request deadlines → typed sheds
   at submit, EDF ordering, pool-starved preemption on a deliberately
   undersized block pool) and the **unbounded baseline** (everything
   admitted FIFO, deadlines tracked host-side only). Past capacity
   the unbounded backlog grows without bound, so late arrivals blow
   through their deadlines and goodput (tokens of deadline-hitting
   requests per second) decays — while the bounded policy sheds the
   excess at admission and holds goodput ~flat. Records shed rate,
   preemption rate, deadline-hit rate, goodput tok/s, and p95
   latency per (multiple, policy); ``engine.audit()`` asserts the
   block-pool invariants after every storm.
   ``scheduler.simulate_overload`` is the analytic twin — the JSON
   records whether it predicts the measured shed-rate ordering.
   Emitted as the JSON's ``overload`` section.

Emits ``BENCH_serving.json`` at the repo root (tok/s per K, the K8/K1
speedup, the chunked/stall mixed-workload ratio, the precision table +
greedy equivalence bits) so future PRs have a perf trajectory to
regress against. Sections are merged into an existing file, so running
one sweep never clobbers another's numbers.

Methodology (standing note, enforced since PR 9): every timed decode
region auto-extends its pass count until it spans at least
``MIN_TIMED_S`` (0.15 s) — shorter regions measured 0.63-1.49x
run-to-run swings on this shared container — and each section records
the achieved duration (``decode_wall_s``) plus the pass count it took
(``timed_passes``).
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import time
from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.quant.quantize import QuantizedTensor
from repro.serving import (Request, SamplingConfig, ServingEngine,
                           SubmitReject)

KS = (1, 4, 8, 16)
N_REQUESTS = 32
MAX_NEW = 48
SLOTS = 4
REPS = 3

# bench methodology floor (PR-3 standing note, enforced here): timed
# decode regions below this duration swung 0.63-1.49x run-to-run on
# this shared container, so any region that comes up short auto-extends
# its pass count until it clears the bar (see _timed_region)
MIN_TIMED_S = 0.15

# precision sweep: the §5.3 ladder through the serving engine; K=1
# isolates per-dispatch cost per format, K=8 is the amortized serving
# operating point where the memory-roofline win should show. Workload
# matches the megastep sweep (the timed decode region must be long
# enough to dominate scheduler noise on a shared container).
PRECISIONS = ("bf16", "q8_0", "q4_0")
PREC_KS = (1, 8)
PREC_REQUESTS = 32
PREC_MAX_NEW = 48
PREC_REPS = 3

# kv-cache precision sweep (paper §5.3 applied to the *other* decode
# stream): long-context operating point — prompts 40-56 tokens into a
# 192-position cache, 48 generated tokens — so the per-step cache read
# is non-negligible next to the weight stream on this toy model. K=1
# isolates the dispatch floor per cache format; K=8 is the amortized
# serving point where a bandwidth win can show. Sized so the timed
# decode region stays ≥0.15 s (PR-3 methodology note: shorter regions
# swung 0.63-1.49x run-to-run on this shared container).
KV_PRECISIONS = ("bf16", "q8_0", "q4_0")
KV_KS = (1, 8)
KV_REQUESTS = 32
KV_MAX_NEW = 48
KV_MAX_LEN = 192
KV_PROMPT_RANGE = (40, 57)
KV_REPS = 3

# kernel-backend sweep: quantized weights + quantized cache served
# through the fused Pallas dequant kernels (quant_matmul +
# decode_attention_quant) vs the materialized-unpack XLA fallback.
# On this CPU container Pallas runs in interpret mode, so the *wall
# numbers are not the TPU story* — the recorded claims are (a) greedy
# token-identity across backends (the engine contract the kernels were
# built against) and (b) the analytic q4-vs-q8 ordering flip on
# TPU-class bandwidth, which only the fused backend produces.
KB_FORMATS = ("q8_0", "q4_0")
KB_BACKENDS = ("xla", "pallas")
KB_K = 8
KB_REQUESTS = 16
KB_MAX_NEW = 32
KB_MAX_LEN = 128
KB_PROMPT_RANGE = (24, 41)
KB_REPS = 2

# async-overlap sweep: serial vs pipelined dispatch/drain loop at the
# paper's K=1 per-token-dispatch operating point (at larger K the
# megastep has already amortized the host gap this sweep hides — see
# the module docstring). Chunked admission (the pipelined loop's
# steady state: admissions staged during megastep N ride into N+1's
# slot view); donation off because chained-carry donation serializes
# dispatch on this backend. One engine serves every depth — the knob
# is host-side orchestration over the same compiled executable — so
# the comparison can't be confounded by separate jit caches. Sized so
# the timed decode region stays ≥0.15 s (PR-3 methodology note).
# 16 long-generation requests = 4 retirement waves on 4 slots: the
# stale-view tax (a retiring slot idles up to depth-1 extra substeps
# before the host sees it) stays small next to the steady-state loop
ASYNC_DEPTHS = (1, 2, 4)
ASYNC_REQUESTS = 16
ASYNC_MAX_NEW = 96
ASYNC_K = 1
ASYNC_REPS = 5

# paging sweep: dense vs paged cache through the engine. Loads grow so
# the peak live token count grows while the dense prealloc stays fixed
# — the "cache bytes scale with live tokens" claim measured, not
# asserted. The prefix leg's workload is Xiao et al.'s mobile shape:
# every request opens with the same system prefix, unique tail after.
PAGE_SIZES = (8, 16, 32)
PAGING_MAX_LEN = 128
PAGING_MAX_NEW = 32
PAGING_PROMPT_RANGE = (20, 37)
PAGING_LOADS = (2, 4, 12)      # requests per load point (4 slots)
PAGING_REPS = 2
PAGING_PREFIX_LEN = 24         # shared system-prompt head
PAGING_PREFIX_REQUESTS = 12

# mixed workload: admission-heavy traffic (short prompts, short
# generations, ~2 arrivals per megastep → every megastep boundary has
# admissions pending, but riding stays within slot capacity) — the
# stall-vs-chunked comparison's operating point
MIX_REQUESTS = 96
MIX_MAX_NEW = 6
MIX_K = 8
MIX_REPS = 5

# overload sweep: Poisson arrivals past measured capacity. The block
# pool is deliberately undersized (12 usable blocks vs a 4-slot x
# 3-4-page worst case) so pool-starved admissions exercise EDF
# preemption, and per-request deadlines vary so urgent late arrivals
# hold strictly-earlier EDF keys than lax residents (the victim
# eligibility rule). Deadlines are drawn relative to the *measured*
# aggregate service time so the operating point self-calibrates to
# whatever this container runs at: the bounded queue's worst-case wait
# (~queue_bound x service) must straddle the deadline band for the
# policies to separate.
OV_SLOTS = 4
OV_K = 8
OV_MAX_LEN = 64
OV_MAX_NEW = 12
OV_PROMPT_RANGE = (8, 21)
OV_PAGE = 8
OV_BLOCKS = 13                  # 12 usable: < slots x 4-page worst case
OV_QUEUE_BOUND = 2 * OV_SLOTS
OV_MULTIPLES = (1.0, 2.0, 3.0)  # x measured capacity
OV_REQUESTS = 40                # arrivals per replay pass
OV_DEADLINE_RANGE = (6.0, 14.0)  # x measured service_s, per request
OV_SEED = 7


def _requests(n: int = N_REQUESTS, max_new: int = MAX_NEW):
    return [Request(uid=i, prompt=np.arange(5 + i % 8, dtype=np.int32) + 1,
                    max_new_tokens=max_new) for i in range(n)]


def _pass(engine, n: int = N_REQUESTS, max_new: int = MAX_NEW):
    """One full pass over the standard workload. Returns (end-to-end
    wall, decode-phase wall, decode tokens, total tokens, outputs)."""
    reqs = _requests(n, max_new)
    for r in reqs:
        engine.submit(r)
    tokens0 = engine.stats.tokens_generated
    prefills0 = engine.stats.prefills
    decode0 = engine.stats.decode_wall_s
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0
    tokens = engine.stats.tokens_generated - tokens0
    dec_tokens = tokens - (engine.stats.prefills - prefills0)
    return (dt, engine.stats.decode_wall_s - decode0, dec_tokens,
            tokens, [r.output for r in reqs])


def _timed_region(pass_fn, timed_idx: int = 0, *,
                  min_s: float = MIN_TIMED_S, passes: int = 0,
                  max_passes: int = 64):
    """One timed region built from consecutive passes of ``pass_fn``.

    ``pass_fn`` returns a tuple of numeric wall/token measurements
    with the pass outputs last; the numerics are summed across passes
    and the region keeps extending until the wall at ``timed_idx``
    reaches ``min_s`` (the bench methodology floor — see module
    docstring). ``passes`` > 0 pins a *minimum* pass count (so
    best-of reps compare near-identical workloads), but the ``min_s``
    floor still applies: a rep that comes in faster than the first
    one keeps extending rather than recording an under-floor region.
    Returns ``(*summed_numerics, outputs, n_passes)``.
    """
    totals, outputs, n = None, None, 0
    while (n == 0 or n < passes
           or (totals[timed_idx] < min_s and n < max_passes)):
        res = pass_fn()
        outputs = res[-1]
        nums = res[:-1]
        totals = nums if totals is None else \
            tuple(a + b for a, b in zip(totals, nums))
        n += 1
    return (*totals, outputs, n)


def _mixed_trace(cfg, seed: int = 0):
    """Deterministic Poisson-ish arrival trace: (arrival_tick, Request)
    pairs, arrival measured in engine steps so both admission modes
    replay the identical schedule. Prompt lengths vary across buckets
    so stall admission pays realistically-fragmented dispatches."""
    rng = np.random.default_rng(seed)
    trace = []
    tick = 0
    for i in range(MIX_REQUESTS):
        plen = int(rng.integers(3, 14))
        prompt = rng.integers(1, cfg.vocab_size,
                              size=plen).astype(np.int32)
        trace.append((tick, Request(uid=i, prompt=prompt,
                                    max_new_tokens=MIX_MAX_NEW)))
        tick += int(rng.integers(0, 2))
    return trace


def _run_mixed(engine, cfg, seed: int = 0):
    """Replay the arrival trace. Returns (wall, decode tokens, total
    tokens, dispatches, outputs)."""
    trace = collections.deque(_mixed_trace(cfg, seed))
    n_req = len(trace)
    reqs = [r for _, r in trace]
    mega0 = engine.stats.megasteps
    pf0 = engine.stats.prefill_batches
    tok0 = engine.stats.tokens_generated
    tick = 0
    t0 = time.perf_counter()
    while trace or engine.queue or any(
            r is not None for r in engine.active):
        while trace and trace[0][0] <= tick:
            engine.submit(trace.popleft()[1])
        engine.step()
        tick += 1
    wall = time.perf_counter() - t0
    tokens = engine.stats.tokens_generated - tok0
    dispatches = (engine.stats.megasteps - mega0 +
                  engine.stats.prefill_batches - pf0)
    return wall, tokens - n_req, tokens, dispatches, \
        [r.output for r in reqs]


def _build_model():
    # batch-1-style decode on a small model is the dispatch-bound regime
    # the paper's §5 measures; keep the device step small so the sweep
    # exposes the launch-overhead amortization rather than raw FLOPs
    cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=1,
                  unroll_scans=True)   # 2 layers: unroll beats while-loop
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _param_bytes(params) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor):
            total += leaf.quant_nbytes
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total


def _sweep_precision(cfg, model, params, out, rows) -> None:
    """{bf16, q8_0, q4_0} × K ∈ {1, 8} through the megastep engine —
    the paper's §5.3 precision table as a serving measurement."""
    from repro.quant.quantize import quantize_tree
    # quantize once per format; every engine of that format shares the
    # tree (the engine's matching-policy path is a no-op)
    params_by_fmt = {
        fmt: (params if fmt == "bf16"
              else quantize_tree(params, fmt, cfg.quant_group))
        for fmt in PRECISIONS}
    engines = {
        (fmt, k): ServingEngine(model, params_by_fmt[fmt], slots=SLOTS,
                                max_len=64,
                                sampling=SamplingConfig(),  # greedy
                                megastep_k=k, admission="stall",
                                megastep_unroll=True, quant_policy=fmt)
        for fmt in PRECISIONS for k in PREC_KS}
    # best-of per metric independently (same methodology as the
    # megastep sweep: a rep with the best decode phase may have a
    # noisy prefill phase and vice versa)
    best_dt = {key: float("inf") for key in engines}
    best_dec = {key: float("inf") for key in engines}
    tokens, dec_tokens, outputs, n_passes = {}, {}, {}, {}
    for key, eng in engines.items():             # untimed: compilation
        _pass(eng, PREC_REQUESTS, PREC_MAX_NEW)
        eng.reset()
    for _ in range(PREC_REPS):                   # interleave reps so
        for key, eng in engines.items():         # load hits all alike
            dt, dec_dt, dec_tokens[key], tokens[key], outputs[key], \
                n = _timed_region(
                    lambda e=eng: _pass(e, PREC_REQUESTS,
                                        PREC_MAX_NEW),
                    1, passes=n_passes.get(key, 0))
            n_passes[key] = n
            best_dt[key] = min(best_dt[key], dt)
            best_dec[key] = min(best_dec[key], dec_dt)
            eng.reset()

    bf16_bytes = _param_bytes(params)
    formats: Dict[str, Dict] = {}
    for fmt in PRECISIONS:
        per_k = {}
        for k in PREC_KS:
            key = (fmt, k)
            per_k[f"k{k}"] = {
                "decode_tok_s": round(dec_tokens[key] / best_dec[key], 1),
                "tok_s": round(tokens[key] / best_dt[key], 1),
                "decode_wall_s": round(best_dec[key], 4),
                "tokens": tokens[key],
                "timed_passes": n_passes[key],
            }
        qbytes = _param_bytes(params_by_fmt[fmt])
        formats[fmt] = {
            **per_k,
            "weight_bytes": qbytes,
            "weight_bytes_ratio": round(qbytes / bf16_bytes, 3),
            # greedy K-invariance must hold *within* a format (the
            # engine contract); tokens may differ across formats
            "greedy_equiv_k8_k1":
                outputs[(fmt, 1)] == outputs[(fmt, 8)],
        }

    q4 = formats["q4_0"]["k8"]["decode_tok_s"]
    b16 = formats["bf16"]["k8"]["decode_tok_s"]

    # analytic twin: the §5.3 memory-roofline prediction for the same
    # sweep on the paper's 2-thread A17 CPU operating point
    from repro.core import a17_cpu, simulate_precision
    sim = simulate_precision(cfg, a17_cpu(2), kv_len=48,
                             formats=PRECISIONS, ks=PREC_KS)
    analytic = {fmt: {f"k{k}": round(sim[fmt][k].tokens_per_s, 1)
                      for k in PREC_KS} for fmt in PRECISIONS}

    out["precision"] = {
        "requests": PREC_REQUESTS, "max_new": PREC_MAX_NEW,
        "slots": SLOTS, "sampling": "greedy", "admission": "stall",
        "min_timed_s": MIN_TIMED_S,
        "formats": formats,
        "q4_over_bf16_k8_decode": round(q4 / b16, 2),
        "q8_over_bf16_k8_decode": round(
            formats["q8_0"]["k8"]["decode_tok_s"] / b16, 2),
        "analytic_a17_2t": {
            **analytic,
            "q4_over_f16_k8": round(
                analytic["q4_0"]["k8"] / analytic["bf16"]["k8"], 2)},
    }
    rows.append((
        "serving/precision_q4_over_bf16_k8", q4 / b16 * 100,
        f"q4_0 {q4:.0f} vs bf16 {b16:.0f} decode tok/s at K=8 "
        f"(= {q4 / b16:.2f}x; analytic a17-2t predicts "
        f"{out['precision']['analytic_a17_2t']['q4_over_f16_k8']:.2f}x; "
        f"weight bytes ratio "
        f"{formats['q4_0']['weight_bytes_ratio']:.3f})"))


def _kv_requests(cfg, seed: int = 7):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(
                        1, cfg.vocab_size,
                        size=int(rng.integers(*KV_PROMPT_RANGE))
                    ).astype(np.int32),
                    max_new_tokens=KV_MAX_NEW)
            for i in range(KV_REQUESTS)]


def _kv_pass(engine, cfg):
    """One pass over the long-context workload. Returns (decode wall,
    decode tokens, total tokens, outputs)."""
    reqs = _kv_requests(cfg)
    for r in reqs:
        engine.submit(r)
    tokens0 = engine.stats.tokens_generated
    prefills0 = engine.stats.prefills
    decode0 = engine.stats.decode_wall_s
    engine.run()
    tokens = engine.stats.tokens_generated - tokens0
    dec_tokens = tokens - (engine.stats.prefills - prefills0)
    return (engine.stats.decode_wall_s - decode0, dec_tokens, tokens,
            [r.output for r in reqs])


def _sweep_kv(cfg, model, params, out, rows) -> None:
    """{bf16, q8_0, q4_0} cache × K ∈ {1, 8} through the megastep
    engine at long context — decode tok/s + the measured cache-bytes
    ratio (≈ bits/16 per format: int8 payload + groupwise scales)."""
    engines = {
        (fmt, k): ServingEngine(model, params, slots=SLOTS,
                                max_len=KV_MAX_LEN,
                                sampling=SamplingConfig(),  # greedy
                                megastep_k=k, admission="stall",
                                megastep_unroll=True, kv_quant=fmt)
        for fmt in KV_PRECISIONS for k in KV_KS}
    best_dec = {key: float("inf") for key in engines}
    tokens, dec_tokens, outputs, n_passes = {}, {}, {}, {}
    for key, eng in engines.items():             # untimed: compilation
        _kv_pass(eng, cfg)
        eng.reset()
    for _ in range(KV_REPS):                     # interleave reps so
        for key, eng in engines.items():         # load hits all alike
            dec_dt, dec_tokens[key], tokens[key], outputs[key], n = \
                _timed_region(lambda e=eng: _kv_pass(e, cfg), 0,
                              passes=n_passes.get(key, 0))
            n_passes[key] = n
            best_dec[key] = min(best_dec[key], dec_dt)
            eng.reset()

    bf16_cache = engines[("bf16", 1)].cache_nbytes()
    formats: Dict[str, Dict] = {}
    for fmt in KV_PRECISIONS:
        per_k = {}
        for k in KV_KS:
            key = (fmt, k)
            per_k[f"k{k}"] = {
                "decode_tok_s": round(dec_tokens[key] / best_dec[key], 1),
                "decode_wall_s": round(best_dec[key], 4),
                "tokens": tokens[key],
                "timed_passes": n_passes[key],
            }
        cbytes = engines[(fmt, 1)].cache_nbytes()
        formats[fmt] = {
            **per_k,
            "cache_bytes": cbytes,
            "cache_bytes_ratio": round(cbytes / bf16_cache, 4),
            # greedy K-invariance must hold *within* a cache format
            # (the engine contract); tokens may differ across formats
            # (cache roundtrip drift is legal, reference-pinned in the
            # property suite)
            "greedy_equiv_k8_k1":
                outputs[(fmt, 1)] == outputs[(fmt, 8)],
        }

    b16 = formats["bf16"]["k8"]["decode_tok_s"]
    q8 = formats["q8_0"]["k8"]["decode_tok_s"]
    q4 = formats["q4_0"]["k8"]["decode_tok_s"]

    # analytic twin: the cache-stream prediction at this toy context
    # and at paper-scale long context on the 2-thread A17 point
    from repro.core import a17_cpu, simulate_kv_precision
    sim = simulate_kv_precision(cfg, a17_cpu(2), ks=KV_KS,
                                kv_lens=(KV_MAX_LEN, 32768))
    analytic = {fmt: {f"ctx{kvl}": {
        f"k{k}": round(sim[fmt][kvl][k].tokens_per_s, 2) for k in KV_KS}
        for kvl in (KV_MAX_LEN, 32768)} for fmt in KV_PRECISIONS}

    out["kv_precision"] = {
        "requests": KV_REQUESTS, "max_new": KV_MAX_NEW,
        "max_len": KV_MAX_LEN,
        "prompt_len": f"{KV_PROMPT_RANGE[0]}-{KV_PROMPT_RANGE[1] - 1}",
        "slots": SLOTS, "sampling": "greedy", "admission": "stall",
        "min_timed_s": MIN_TIMED_S,
        "formats": formats,
        "q8_over_bf16_k8_decode": round(q8 / b16, 2),
        "q4_over_bf16_k8_decode": round(q4 / b16, 2),
        "analytic_a17_2t": analytic,
    }
    rows.append((
        "serving/kv_q8_over_bf16_k8", q8 / b16 * 100,
        f"q8_0 cache {q8:.0f} vs bf16 {b16:.0f} decode tok/s at K=8 "
        f"long-context (= {q8 / b16:.2f}x; cache bytes ratio "
        f"{formats['q8_0']['cache_bytes_ratio']:.3f}; q4_0 "
        f"{q4 / b16:.2f}x at {formats['q4_0']['cache_bytes_ratio']:.3f})"))


def _kb_requests(cfg, seed: int = 11):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(
                        1, cfg.vocab_size,
                        size=int(rng.integers(*KB_PROMPT_RANGE))
                    ).astype(np.int32),
                    max_new_tokens=KB_MAX_NEW)
            for i in range(KB_REQUESTS)]


def _kb_pass(engine, cfg):
    reqs = _kb_requests(cfg)
    for r in reqs:
        engine.submit(r)
    tokens0 = engine.stats.tokens_generated
    prefills0 = engine.stats.prefills
    decode0 = engine.stats.decode_wall_s
    engine.run()
    tokens = engine.stats.tokens_generated - tokens0
    dec_tokens = tokens - (engine.stats.prefills - prefills0)
    return (engine.stats.decode_wall_s - decode0, dec_tokens, tokens,
            [r.output for r in reqs])


def _sweep_kernels(cfg, model, params, out, rows) -> None:
    """{q8_0, q4_0} weights+cache × {xla, pallas} kernel backends
    through the megastep engine, plus the analytic backend flip."""
    from repro.quant.quantize import quantize_tree
    params_by_fmt = {
        fmt: quantize_tree(params, fmt, cfg.quant_group)
        for fmt in KB_FORMATS}
    engines = {
        (fmt, be): ServingEngine(model, params_by_fmt[fmt], slots=SLOTS,
                                 max_len=KB_MAX_LEN,
                                 sampling=SamplingConfig(),  # greedy
                                 megastep_k=KB_K, admission="stall",
                                 megastep_unroll=True, quant_policy=fmt,
                                 kv_quant=fmt, kernels=be)
        for fmt in KB_FORMATS for be in KB_BACKENDS}
    best_dec = {key: float("inf") for key in engines}
    tokens, dec_tokens, outputs, n_passes = {}, {}, {}, {}
    for key, eng in engines.items():             # untimed: compilation
        _kb_pass(eng, cfg)
        eng.reset()
    for _ in range(KB_REPS):                     # interleave reps so
        for key, eng in engines.items():         # load hits all alike
            dec_dt, dec_tokens[key], tokens[key], outputs[key], n = \
                _timed_region(lambda e=eng: _kb_pass(e, cfg), 0,
                              passes=n_passes.get(key, 0))
            n_passes[key] = n
            best_dec[key] = min(best_dec[key], dec_dt)
            eng.reset()

    formats: Dict[str, Dict] = {}
    for fmt in KB_FORMATS:
        per_be = {}
        for be in KB_BACKENDS:
            key = (fmt, be)
            per_be[be] = {
                "decode_tok_s": round(dec_tokens[key] / best_dec[key], 1),
                "decode_wall_s": round(best_dec[key], 4),
                "tokens": tokens[key],
                "timed_passes": n_passes[key],
            }
        formats[fmt] = {
            **per_be,
            # the kernel contract this PR's parity suite pins: the
            # fused dequant kernels are greedy token-identical to the
            # XLA unpack path, so backend choice is pure performance
            "greedy_equiv_xla_pallas":
                outputs[(fmt, "xla")] == outputs[(fmt, "pallas")],
        }

    # analytic twin on TPU-class bandwidth: the planner prices both
    # backends; the fused kernels flip the q4-vs-q8 ordering (this is
    # the prediction a real-pod run would measure, not the interpret-
    # mode walls above)
    from repro.configs import INPUT_SHAPES, get_config as _get
    from repro.core import TPU_V5E, plan as _plan
    full = _get("deepseek-7b")
    plans = {be: _plan(full, INPUT_SHAPES["decode_32k"], TPU_V5E,
                       kernel_backend=be) for be in KB_BACKENDS}
    analytic = {be: {"quant_policy": plans[be].quant_policy,
                     "kv_quant": plans[be].kv_quant}
                for be in KB_BACKENDS}
    flip = (analytic["pallas"]["kv_quant"] == "q4_0"
            and analytic["xla"]["kv_quant"] == "q8_0")

    out["kernel_backend"] = {
        "requests": KB_REQUESTS, "max_new": KB_MAX_NEW,
        "max_len": KB_MAX_LEN, "megastep_k": KB_K, "slots": SLOTS,
        "sampling": "greedy", "admission": "stall",
        "min_timed_s": MIN_TIMED_S,
        "note": "pallas timings are interpret-mode on this CPU "
                "container; the portable claims are token-identity "
                "and the analytic ordering flip",
        "formats": formats,
        "analytic_tpu_v5e_decode_32k": analytic,
        "q4_flip_predicted": flip,
    }
    q4x = formats["q4_0"]["xla"]["decode_tok_s"]
    q4p = formats["q4_0"]["pallas"]["decode_tok_s"]
    rows.append((
        "serving/kernels_q4_pallas_over_xla", q4p / q4x * 100,
        f"q4_0 weights+cache: pallas {q4p:.0f} vs xla {q4x:.0f} decode "
        f"tok/s (interpret mode); token-identical: "
        f"{formats['q4_0']['greedy_equiv_xla_pallas']}; TPU planner "
        f"flip xla->q8_0 / pallas->q4_0: {flip}"))


def _sweep_megastep(cfg, model, params, out, rows) -> None:
    engines = {k: ServingEngine(model, params, slots=SLOTS, max_len=64,
                                sampling=SamplingConfig(),  # greedy →
                                megastep_k=k,               # comparable
                                admission="stall",   # PR-1 upfront-queue
                                megastep_unroll=True)
               for k in KS}
    best = {k: float("inf") for k in KS}
    best_dec = {k: float("inf") for k in KS}
    outputs, tokens, dec_tokens, n_passes = {}, {}, {}, {}
    for k in KS:                         # untimed pass pays compilation
        _pass(engines[k])
    for _ in range(REPS):                # interleave reps across K so
        for k in KS:                     # machine load hits all K alike
            dt, dec_dt, dec_tokens[k], tokens[k], outputs[k], n = \
                _timed_region(lambda e=engines[k]: _pass(e), 1,
                              passes=n_passes.get(k, 0))
            n_passes[k] = n
            best[k] = min(best[k], dt)
            best_dec[k] = min(best_dec[k], dec_dt)

    per_k = {}
    for k in KS:
        dt, dec_dt = best[k], best_dec[k]
        tok_s = tokens[k] / dt
        # decode-phase throughput isolates the dispatch-amortization
        # lever the sweep is about (prefill cost is identical across K)
        dec_tok_s = dec_tokens[k] / dec_dt
        total_passes = 1 + REPS * n_passes[k]
        dispatches = engines[k].stats.megasteps // total_passes
        per_k[k] = {"tok_s": round(tok_s, 1),
                    "decode_tok_s": round(dec_tok_s, 1),
                    "wall_s": round(dt, 4),
                    "decode_wall_s": round(dec_dt, 4),
                    "tokens": tokens[k],
                    "timed_passes": n_passes[k],
                    "dispatches": dispatches}
        prefill_batches = engines[k].stats.prefill_batches // total_passes
        rows.append((
            f"serving/megastep_k{k}", dec_dt / max(dispatches, 1) * 1e6,
            f"{tokens[k]} tokens in {dt:.2f}s = {tok_s:.0f} tok/s e2e, "
            f"{dec_tok_s:.0f} tok/s decode-phase "
            f"({prefill_batches} prefill batches)"))

    speedup = per_k[8]["decode_tok_s"] / per_k[1]["decode_tok_s"]
    equiv = outputs[8] == outputs[1]
    out.update({
        "bench": "serving_megastep_sweep",
        "model": "deepseek-7b reduced (2L, d64, ff128, v256)",
        "slots": SLOTS, "requests": N_REQUESTS, "max_new": MAX_NEW,
        "sampling": "greedy", "min_timed_s": MIN_TIMED_S,
        "per_k": {str(k): v for k, v in per_k.items()},
        "k8_over_k1_decode": round(speedup, 2),
        "k8_over_k1_e2e": round(per_k[8]["tok_s"] / per_k[1]["tok_s"], 2),
        "greedy_equiv_k8_k1": equiv,
    })
    rows.append(("serving/k8_over_k1_speedup", speedup * 100,
                 f"K=8 {speedup:.2f}x over K=1 (decode phase); greedy "
                 f"token-identical: {equiv}"))


def _sweep_mixed(cfg, model, params, out, rows) -> None:
    # -- mixed prefill/decode workload: stall vs chunked admission -------
    mix_engines = {
        mode: ServingEngine(model, params, slots=SLOTS, max_len=64,
                            sampling=SamplingConfig(), megastep_k=MIX_K,
                            admission=mode, megastep_unroll=True)
        for mode in ("stall", "chunked")}
    mixed = {}
    mix_outputs = {}
    mix_best = {}
    mix_passes = {}
    for mode, eng in mix_engines.items():
        _run_mixed(eng, cfg)             # untimed pass pays compilation
        eng.reset()
    for _ in range(MIX_REPS):            # interleave reps across modes
        for mode, eng in mix_engines.items():   # so machine load hits
            res = _timed_region(                # both alike
                lambda e=eng: _run_mixed(e, cfg), 0,
                passes=mix_passes.get(mode, 0))
            mix_passes[mode] = res[-1]
            if mode not in mix_best or res[0] < mix_best[mode][0]:
                mix_best[mode] = res
            mix_outputs[mode] = res[4]
            eng.reset()
    for mode in mix_engines:
        wall, dec_tokens, tokens, dispatches, _, n = mix_best[mode]
        mixed[mode] = {
            "decode_tok_s": round(dec_tokens / wall, 1),
            "tok_s": round(tokens / wall, 1),
            "wall_s": round(wall, 4),
            "tokens": tokens,
            "dispatches": dispatches,
            "timed_passes": n,
        }
    mix_ratio = mixed["chunked"]["decode_tok_s"] / \
        mixed["stall"]["decode_tok_s"]
    mix_equiv = mix_outputs["chunked"] == mix_outputs["stall"]

    out["mixed_workload"] = {
        "requests": MIX_REQUESTS, "max_new": MIX_MAX_NEW,
        "megastep_k": MIX_K, "slots": SLOTS,
        "arrivals": "seeded poisson-ish, gap 0-1 steps, "
                    "prompts 3-13 tokens",
        "min_timed_s": MIN_TIMED_S,
        **{mode: mixed[mode] for mode in ("stall", "chunked")},
        "chunked_over_stall_decode": round(mix_ratio, 2),
        "greedy_equiv_chunked_stall": mix_equiv,
    }
    rows.append((
        "serving/chunked_over_stall_mixed", mix_ratio * 100,
        f"mixed workload: chunked admission {mix_ratio:.2f}x over "
        f"stall-prefill decode-phase tok/s "
        f"({mixed['chunked']['dispatches']} vs "
        f"{mixed['stall']['dispatches']} dispatches); greedy "
        f"token-identical: {mix_equiv}"))


def _async_pass(engine) -> Dict:
    """One pass over the standard workload with per-pass deltas of the
    pipelining attribution stats."""
    reqs = _requests(ASYNC_REQUESTS, ASYNC_MAX_NEW)
    for r in reqs:
        engine.submit(r)
    st = engine.stats
    base = (st.decode_wall_s, st.drain_wait_s, st.megasteps,
            st.tokens_generated, st.prefills)
    engine.run()
    tokens = st.tokens_generated - base[3]
    return {
        "decode_wall_s": st.decode_wall_s - base[0],
        "drain_wait_s": st.drain_wait_s - base[1],
        "megasteps": st.megasteps - base[2],
        "dec_tokens": tokens - (st.prefills - base[4]),
        "outputs": [r.output for r in reqs],
    }


def _sweep_async(cfg, model, params, out, rows) -> None:
    """pipeline_depth {1, 2, 4} through one K=1 engine: decode tok/s,
    the per-megastep host dispatch/drain gap and its shrinkage, greedy
    token identity across depths."""
    # This sweep builds its own model, bigger than the shared 2L/d64
    # one: pipelining hides host work *behind the device step*, so the
    # device step must be comparable to the ~0.5-1ms host gap for
    # there to be anything to hide (on the shared model the device is
    # ~15us/megastep at K=1 — the measurable ceiling is ~2%). d256 at
    # 2 layers puts the K=1 device step at ~1ms, the balanced point;
    # much bigger (4L/ff1024) and the host blocks on deep in-flight
    # work instead, which this backend's partial background chaining
    # turns into a regression.
    cfg = reduced(get_config("deepseek-7b"), d_model=256, d_ff=512,
                  vocab_size=512, num_heads=4, num_kv_heads=2,
                  unroll_scans=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, slots=SLOTS, max_len=128,
                        sampling=SamplingConfig(),  # greedy
                        megastep_k=ASYNC_K, admission="chunked",
                        megastep_unroll=True, donate_carries=False)
    _async_pass(eng)                     # untimed pass pays compilation
    eng.reset()

    def _one():
        r = _async_pass(eng)
        return (r["decode_wall_s"], r["drain_wait_s"], r["megasteps"],
                r["dec_tokens"], r["outputs"])

    best = {d: None for d in ASYNC_DEPTHS}
    outputs = {}
    depth_passes = {}
    for _ in range(ASYNC_REPS):          # interleave reps across depths
        for d in ASYNC_DEPTHS:           # so load hits all alike
            eng.pipeline_depth = d
            dec, drain, megasteps, dec_tokens, outs, n = \
                _timed_region(_one, 0, passes=depth_passes.get(d, 0))
            depth_passes[d] = n
            res = {"decode_wall_s": dec, "drain_wait_s": drain,
                   "megasteps": megasteps, "dec_tokens": dec_tokens}
            outputs[d] = outs
            if best[d] is None or \
                    res["decode_wall_s"] < best[d]["decode_wall_s"]:
                best[d] = res
            eng.reset()

    # pipelining must move *time*, never tokens: greedy streams are
    # identical across depths (the property suite pins this across all
    # cache families; the bench asserts it on its own workload too)
    equiv = all(outputs[d] == outputs[ASYNC_DEPTHS[0]]
                for d in ASYNC_DEPTHS)
    assert equiv, "pipelined engine diverged from serial greedy tokens"

    depths: Dict[str, Dict] = {}
    for d in ASYNC_DEPTHS:
        b = best[d]
        m = max(b["megasteps"], 1)
        # the host gap: per-megastep host work (dispatch call, drain
        # python, admission staging) NOT spent blocked on the device —
        # the serial-loop overhead pipelining exists to hide. The
        # blocked share (drain_wait) may grow as depth rises: the host
        # runs ahead and waits on deeper in-flight work instead.
        gap_us = (b["decode_wall_s"] - b["drain_wait_s"]) / m * 1e6
        depths[f"depth{d}"] = {
            "decode_tok_s": round(b["dec_tokens"] / b["decode_wall_s"], 1),
            "decode_wall_s": round(b["decode_wall_s"], 4),
            "megasteps": b["megasteps"],
            "host_gap_us_per_megastep": round(gap_us, 1),
            "drain_wait_us_per_megastep": round(
                b["drain_wait_s"] / m * 1e6, 1),
            "timed_passes": depth_passes[d],
        }
    d_hi = ASYNC_DEPTHS[-1]
    gap1 = depths["depth1"]["host_gap_us_per_megastep"]
    gap_hi = depths[f"depth{d_hi}"]["host_gap_us_per_megastep"]
    ratio = depths[f"depth{d_hi}"]["decode_tok_s"] / \
        depths["depth1"]["decode_tok_s"]

    # analytic twin: the overlap model at the paper's 2-thread A17
    # point, same K — predicted period per megastep per depth (the
    # model saturates at depth 2: one in-flight megastep already hides
    # the gap up to the device-step time)
    from repro.core import a17_cpu, simulate_async_overlap
    sim = simulate_async_overlap(cfg, a17_cpu(2), k=ASYNC_K,
                                 depths=ASYNC_DEPTHS)
    analytic = {f"depth{d}": {
        "tok_s": round(sim[d].tokens_per_s, 1),
        "detail": sim[d].detail} for d in ASYNC_DEPTHS}

    out["async_overlap"] = {
        "model": "deepseek-7b reduced (2L, d256, ff512, v512) — "
                 "sized so the K=1 device step ~= the host gap",
        "requests": ASYNC_REQUESTS, "max_new": ASYNC_MAX_NEW,
        "megastep_k": ASYNC_K, "slots": SLOTS,
        "sampling": "greedy", "admission": "chunked",
        "donate_carries": False, "min_timed_s": MIN_TIMED_S,
        "note": "K=1 is the per-token-dispatch regime this sweep "
                "pipelines; donation is off because chained-carry "
                "donation serializes dispatch on this backend, and at "
                "K>=2 megastep amortization has already hidden the "
                "host gap (see benchmarks/serving_bench.py docstring)",
        "depths": depths,
        "host_gap_shrink": round(gap1 / max(gap_hi, 1e-9), 2),
        f"depth{d_hi}_over_depth1_decode": round(ratio, 2),
        "greedy_equiv_depths": equiv,
        "analytic_a17_2t": analytic,
    }
    rows.append((
        "serving/async_host_gap_depth%d" % d_hi, gap_hi,
        f"host gap/megastep {gap1:.0f}us (depth1) -> {gap_hi:.0f}us "
        f"(depth{d_hi}), {gap1 / max(gap_hi, 1e-9):.2f}x shrink; "
        f"decode {ratio:.2f}x; greedy token-identical: {equiv}"))


def _paging_requests(cfg, n: int, seed: int = 17, prefix_len: int = 0):
    rng = np.random.default_rng(seed)
    prefix = (rng.integers(1, cfg.vocab_size,
                           size=prefix_len).astype(np.int32)
              if prefix_len else None)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(*PAGING_PROMPT_RANGE))
        tail = rng.integers(1, cfg.vocab_size,
                            size=plen).astype(np.int32)
        prompt = np.concatenate([prefix, tail]) if prefix_len else tail
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=PAGING_MAX_NEW))
    return reqs


def _paging_pass(engine, cfg, n: int, prefix_len: int = 0):
    """One timed pass. Returns (decode wall, decode tokens, total
    tokens, outputs) — the _kv_pass shape."""
    reqs = _paging_requests(cfg, n, prefix_len=prefix_len)
    for r in reqs:
        engine.submit(r)
    st = engine.stats
    base = (st.decode_wall_s, st.tokens_generated, st.prefills)
    engine.run()
    tokens = st.tokens_generated - base[1]
    dec_tokens = tokens - (st.prefills - base[2])
    return (st.decode_wall_s - base[0], dec_tokens, tokens,
            [r.output for r in reqs])


def _paging_peak(engine, cfg, n: int):
    """Untimed step-driven pass sampling the pool's peak in-use
    blocks — the live-token footprint the dense layout can't shrink
    below its prealloc."""
    for r in _paging_requests(cfg, n):
        engine.submit(r)
    peak = 0
    while engine.has_work():
        engine.step()
        peak = max(peak, engine.blocks_in_use)
    return peak


def _paged_block_nbytes(engine) -> int:
    """Device bytes of one pool block (all layers, K+V payload +
    scale leaves)."""
    lay = engine.cache["layers"]
    return sum(lay[name].size * lay[name].dtype.itemsize
               // engine.cache_blocks
               for name in ("k", "v", "k_scale", "v_scale")
               if name in lay)


def _sweep_paging(cfg, model, params, out, rows) -> None:
    """Dense vs paged KV cache through the engine: token identity,
    the gather tax, cache bytes scaling with live tokens, and the
    shared-prefix copy-on-write admission win."""
    # pool sized to the workload's worst case, not slots x max_pages:
    # the allocated-bytes win over the dense prealloc is the point
    need = PAGING_PROMPT_RANGE[1] - 1 + PAGING_MAX_NEW
    blocks = {p: SLOTS * -(-need // p) + 1 for p in PAGE_SIZES}
    engines = {0: ServingEngine(model, params, slots=SLOTS,
                                max_len=PAGING_MAX_LEN,
                                sampling=SamplingConfig(),  # greedy
                                megastep_k=MIX_K, admission="chunked",
                                megastep_unroll=True)}
    for p in PAGE_SIZES:
        engines[p] = ServingEngine(model, params, slots=SLOTS,
                                   max_len=PAGING_MAX_LEN,
                                   sampling=SamplingConfig(),
                                   megastep_k=MIX_K,
                                   admission="chunked",
                                   megastep_unroll=True, page_size=p,
                                   cache_blocks=blocks[p])
    n_req = PAGING_LOADS[-1]
    best_dec = {key: float("inf") for key in engines}
    tokens, dec_tokens, outputs, n_passes = {}, {}, {}, {}
    for key, eng in engines.items():             # untimed: compilation
        _paging_pass(eng, cfg, n_req)
        eng.reset()
    for _ in range(PAGING_REPS):                 # interleave reps so
        for key, eng in engines.items():         # load hits all alike
            dec_dt, dec_tokens[key], tokens[key], outputs[key], n = \
                _timed_region(
                    lambda e=eng: _paging_pass(e, cfg, n_req), 0,
                    passes=n_passes.get(key, 0))
            n_passes[key] = n
            best_dec[key] = min(best_dec[key], dec_dt)
            eng.reset()

    dense_bytes = engines[0].cache_nbytes()
    dense_tok_s = dec_tokens[0] / best_dec[0]
    paged: Dict[str, Dict] = {}
    for p in PAGE_SIZES:
        paged[f"p{p}"] = {
            "decode_tok_s": round(dec_tokens[p] / best_dec[p], 1),
            "decode_wall_s": round(best_dec[p], 4),
            "tokens": tokens[p],
            "timed_passes": n_passes[p],
            "cache_bytes": engines[p].cache_nbytes(),
            "cache_blocks": blocks[p],
            # paging moves bytes, never tokens (the tentpole contract,
            # reference-pinned across archs in the property suite)
            "greedy_equiv_dense": outputs[p] == outputs[0],
        }

    # cache bytes vs live tokens: peak in-use pool blocks across
    # growing loads (the dense prealloc never moves)
    p0 = PAGE_SIZES[0]
    eng = engines[p0]
    block_b = _paged_block_nbytes(eng)
    fixed_b = eng.cache_nbytes() - block_b * eng.cache_blocks
    scaling = {}
    for load in PAGING_LOADS:
        eng.reset()
        peak = _paging_peak(eng, cfg, load)
        scaling[f"requests_{load}"] = {
            "peak_blocks": peak,
            "peak_live_tokens_ub": peak * p0,
            "peak_cache_bytes": peak * block_b + fixed_b,
        }
    eng.reset()

    # shared-prefix copy-on-write: every request opens with the same
    # system prompt; hits map its pages into the new slot's table and
    # the riders for those tokens vanish from admission
    pfx = ServingEngine(model, params, slots=SLOTS,
                        max_len=PAGING_MAX_LEN,
                        sampling=SamplingConfig(), megastep_k=MIX_K,
                        admission="chunked", megastep_unroll=True,
                        page_size=p0, prefix_cache=True)
    _paging_pass(pfx, cfg, PAGING_PREFIX_REQUESTS,
                 prefix_len=PAGING_PREFIX_LEN)   # untimed: compilation
    pfx.reset()
    h0 = (pfx.stats.prefix_hits, pfx.stats.prefix_hit_tokens)
    pfx_dec, pfx_dec_tokens, _pt, pfx_out, pfx_n = _timed_region(
        lambda: _paging_pass(pfx, cfg, PAGING_PREFIX_REQUESTS,
                             prefix_len=PAGING_PREFIX_LEN), 0)
    hits = pfx.stats.prefix_hits - h0[0]
    hit_tokens = pfx.stats.prefix_hit_tokens - h0[1]
    dense_out = _paging_pass(engines[0], cfg, PAGING_PREFIX_REQUESTS,
                             prefix_len=PAGING_PREFIX_LEN)[-1]
    prefix = {
        "prefix_len": PAGING_PREFIX_LEN,
        "requests": PAGING_PREFIX_REQUESTS,
        "page_size": p0,
        "decode_tok_s": round(pfx_dec_tokens / pfx_dec, 1),
        "decode_wall_s": round(pfx_dec, 4),
        "timed_passes": pfx_n,
        "prefix_hits": hits,
        "prefix_hit_tokens": hit_tokens,
        # each cached-prefix token is one rider substep the chunked
        # admission no longer spends
        "admission_substeps_saved": hit_tokens,
        "greedy_equiv_dense": pfx_out == dense_out,
    }

    # analytic twin at the paper's 2-thread A17 point, with and
    # without prefix reuse
    from repro.core import a17_cpu
    from repro.core.scheduler import simulate_paging
    mean_prompt = sum(PAGING_PROMPT_RANGE) // 2 + PAGING_PREFIX_LEN
    analytic = {}
    for tag, hit in (("hit0", 0.0), ("hit0.75", 0.75)):
        sim = simulate_paging(cfg, a17_cpu(2), slots=SLOTS, k=MIX_K,
                              prompt_len=mean_prompt,
                              max_new=PAGING_MAX_NEW,
                              kv_len=PAGING_MAX_LEN,
                              page_sizes=PAGE_SIZES, hit_rate=hit)
        analytic[tag] = {
            ("dense" if p == 0 else f"p{p}"): {
                "tok_s": round(r["step"].tokens_per_s, 1),
                "pool_bytes": round(r["pool_bytes"]),
                "rider_substeps_saved": round(
                    r["rider_substeps_saved"], 1)}
            for p, r in sim.items()}

    out["paging"] = {
        "requests": n_req, "max_new": PAGING_MAX_NEW,
        "max_len": PAGING_MAX_LEN, "megastep_k": MIX_K,
        "slots": SLOTS, "sampling": "greedy", "admission": "chunked",
        "min_timed_s": MIN_TIMED_S,
        "page_sizes": list(PAGE_SIZES),
        "dense": {
            "decode_tok_s": round(dense_tok_s, 1),
            "decode_wall_s": round(best_dec[0], 4),
            "tokens": tokens[0],
            "timed_passes": n_passes[0],
            "cache_bytes": dense_bytes,
        },
        "paged": paged,
        "bytes_vs_live_tokens": {
            "page_size": p0,
            "block_bytes": block_b,
            "dense_cache_bytes": dense_bytes,
            **scaling,
        },
        "prefix_cache": prefix,
        "analytic_a17_2t": analytic,
    }
    p8 = paged[f"p{p0}"]
    ratio = p8["decode_tok_s"] / round(dense_tok_s, 1)
    peak_hi = scaling[f"requests_{PAGING_LOADS[-1]}"]["peak_cache_bytes"]
    peak_lo = scaling[f"requests_{PAGING_LOADS[0]}"]["peak_cache_bytes"]
    rows.append((
        "serving/paging_p%d_over_dense" % p0, ratio * 100,
        f"paged p{p0} {p8['decode_tok_s']:.0f} vs dense "
        f"{dense_tok_s:.0f} decode tok/s (= {ratio:.2f}x gather tax); "
        f"token-identical: {p8['greedy_equiv_dense']}; allocated "
        f"{p8['cache_bytes']} vs dense {dense_bytes} bytes"))
    rows.append((
        "serving/paging_bytes_scaling", peak_hi / max(peak_lo, 1) * 100,
        f"peak live cache bytes {peak_lo} -> {peak_hi} as load "
        f"{PAGING_LOADS[0]} -> {PAGING_LOADS[-1]} requests (dense "
        f"fixed at {dense_bytes}); prefix cache: {hits} hits / "
        f"{hit_tokens} prompt tokens skipped, token-identical: "
        f"{prefix['greedy_equiv_dense']}"))


def _overload_calibrate(eng, cfg, *, min_s: float = 0.0):
    """Measure aggregate capacity: saturated queue, no deadlines.
    Returns (service_s per request, megasteps per request, passes)."""
    wall, steps, n, passes = 0.0, 0, 0, 0
    while passes == 0 or wall < min_s:
        eng.reset()
        rng = np.random.default_rng(OV_SEED)
        reqs = [Request(uid=i, prompt=rng.integers(
                    1, cfg.vocab_size, size=int(rng.integers(
                        *OV_PROMPT_RANGE))).astype(np.int32),
                    max_new_tokens=OV_MAX_NEW)
                for i in range(3 * OV_SLOTS)]
        for r in reqs:
            eng.submit(r)
        m0 = eng.stats.megasteps
        t0 = time.perf_counter()
        eng.run()
        wall += time.perf_counter() - t0
        steps += eng.stats.megasteps - m0
        n += len(reqs)
        passes += 1
    return wall / n, steps / n, passes


def _overload_trace(cfg, rng, lam, service_s):
    """Poisson arrivals in megastep ticks: (tick, prompt, deadline_s,
    uid). ``lam`` = arrivals per tick; deadlines are seconds (the
    engine's submit() semantics), drawn relative to measured service."""
    trace, t = [], 0.0
    for i in range(OV_REQUESTS):
        plen = int(rng.integers(*OV_PROMPT_RANGE))
        prompt = rng.integers(1, cfg.vocab_size,
                              size=plen).astype(np.int32)
        dl = float(rng.uniform(*OV_DEADLINE_RANGE)) * service_s
        trace.append((int(t), prompt, dl, i))
        t += rng.exponential(1.0 / lam)
    return trace


def _overload_replay(eng, trace, *, bounded: bool):
    """Replay one arrival trace. Bounded submits with deadlines (typed
    sheds counted, never fatal); unbounded submits without (nothing
    shed, nothing preempted) and scores the same deadlines host-side."""
    pend = collections.deque(trace)
    live = []                    # [req, deadline_s, t_submit, t_done]
    shed = 0
    pre0 = eng.stats.preemptions
    tick = 0
    t0 = time.perf_counter()
    while pend or eng.has_work():
        while pend and pend[0][0] <= tick:
            _, prompt, dl, uid = pend.popleft()
            req = Request(uid=uid, prompt=prompt,
                          max_new_tokens=OV_MAX_NEW,
                          deadline_s=dl if bounded else None)
            try:
                eng.submit(req)
            except SubmitReject:
                shed += 1
                continue
            live.append([req, dl, time.perf_counter(), None])
        eng.step()
        now = time.perf_counter()
        for e in live:
            if e[3] is None and e[0].done:
                e[3] = now
        tick += 1
    wall = time.perf_counter() - t0
    hit = [e for e in live if e[3] is not None and e[3] - e[2] <= e[1]]
    return {
        "wall": wall, "shed": shed, "admitted": len(live),
        "done": sum(1 for e in live if e[3] is not None),
        "hits": len(hit),
        "good_tokens": sum(len(e[0].output) for e in hit),
        "tokens": sum(len(e[0].output) for e in live),
        "preempts": eng.stats.preemptions - pre0,
        "latencies": [e[3] - e[2] for e in live if e[3] is not None],
    }


def _overload_point(eng, cfg, lam, service_s, *, bounded: bool):
    """One (arrival multiple, policy) measurement on the ≥MIN_TIMED_S
    floor; traces are seeded per pass so both policies replay identical
    arrival schedules, prompts, and deadlines."""
    tot = collections.Counter()
    lats, passes = [], 0
    while passes == 0 or tot["wall"] < MIN_TIMED_S:
        eng.reset()
        eng.max_queue = OV_QUEUE_BOUND if bounded else 0
        rng = np.random.default_rng(OV_SEED + 1 + passes)
        trace = _overload_trace(cfg, rng, lam, service_s)
        r = _overload_replay(eng, trace, bounded=bounded)
        lats += r.pop("latencies")
        tot.update(r)
        eng.audit()              # pool invariants hold after the storm
        passes += 1
    offered = tot["shed"] + tot["admitted"]
    return {
        "shed_rate": round(tot["shed"] / offered, 3),
        "preempt_rate": round(tot["preempts"] / max(tot["admitted"], 1),
                              3),
        "deadline_hit_rate": round(tot["hits"] / offered, 3),
        "goodput_tok_s": round(tot["good_tokens"] / tot["wall"], 1),
        "decode_tok_s": round(tot["tokens"] / tot["wall"], 1),
        "p95_latency_s": (round(float(np.percentile(lats, 95)), 4)
                          if lats else None),
        "offered": offered,
        "completed": tot["done"],
        "preemptions": tot["preempts"],
        "decode_wall_s": round(tot["wall"], 4),
        "timed_passes": passes,
    }


def _sweep_overload(cfg, model, params, out, rows) -> None:
    """Bounded admission (max_queue + deadlines + preemption) vs the
    unbounded baseline under Poisson arrivals past measured capacity:
    the overload-PR acceptance claim, measured."""
    eng = ServingEngine(model, params, slots=OV_SLOTS,
                        max_len=OV_MAX_LEN, sampling=SamplingConfig(),
                        megastep_k=OV_K, admission="chunked",
                        megastep_unroll=True, page_size=OV_PAGE,
                        cache_blocks=OV_BLOCKS)
    _overload_calibrate(eng, cfg)            # untimed: compilation
    service_s, steps_per_req, cal_passes = _overload_calibrate(
        eng, cfg, min_s=MIN_TIMED_S)
    capacity_rps = 1.0 / service_s

    points: Dict[str, Dict] = {}
    for mult in OV_MULTIPLES:
        lam = mult / steps_per_req           # arrivals per megastep
        pt = {"arrival_rps": round(mult * capacity_rps, 2)}
        for tag, bounded in (("bounded", True), ("unbounded", False)):
            pt[tag] = _overload_point(eng, cfg, lam, service_s,
                                      bounded=bounded)
        points[f"x{mult:g}"] = pt
    eng.max_queue = 0

    # analytic twin at the paper's 2-thread A17 point: does the napkin
    # model predict the measured shed-rate ordering across multiples?
    from repro.core import a17_cpu
    from repro.core.scheduler import simulate_overload
    sim = simulate_overload(cfg, a17_cpu(2), slots=OV_SLOTS, k=OV_K,
                            prompt_len=sum(OV_PROMPT_RANGE) // 2,
                            max_new=OV_MAX_NEW, page_size=OV_PAGE,
                            cache_blocks=OV_BLOCKS,
                            arrival_multiples=OV_MULTIPLES)
    pred_shed = [round(sim["sweep"][m]["bounded"]["shed_rate"], 3)
                 for m in OV_MULTIPLES]
    meas_shed = [points[f"x{m:g}"]["bounded"]["shed_rate"]
                 for m in OV_MULTIPLES]
    order_ok = (
        all(a <= b for a, b in zip(pred_shed, pred_shed[1:]))
        and all(a <= b for a, b in zip(meas_shed, meas_shed[1:]))
        and ((pred_shed[-1] > pred_shed[0])
             == (meas_shed[-1] > meas_shed[0])))

    g2b = points["x2"]["bounded"]["goodput_tok_s"]
    g2u = points["x2"]["unbounded"]["goodput_tok_s"]
    out["overload"] = {
        "slots": OV_SLOTS, "megastep_k": OV_K, "max_len": OV_MAX_LEN,
        "max_new": OV_MAX_NEW, "page_size": OV_PAGE,
        "cache_blocks": OV_BLOCKS, "queue_bound": OV_QUEUE_BOUND,
        "admission": "chunked", "sampling": "greedy",
        "arrivals_per_pass": OV_REQUESTS,
        "deadline_range_x_service": list(OV_DEADLINE_RANGE),
        "min_timed_s": MIN_TIMED_S,
        "capacity": {
            "service_s_per_request": round(service_s, 5),
            "capacity_rps": round(capacity_rps, 2),
            "megasteps_per_request": round(steps_per_req, 3),
            "calibration_passes": cal_passes,
        },
        "sweep": points,
        "analytic_a17_2t": {
            "capacity_rps": round(sim["capacity"]["capacity_rps"], 3),
            "max_live_requests": sim["capacity"]["max_live_requests"],
            "predicted_bounded_shed_rate": dict(
                zip([f"x{m:g}" for m in OV_MULTIPLES], pred_shed)),
        },
        "predicted_shed_order_matches": order_ok,
        "bounded_beats_unbounded_at_2x": g2b > g2u,
    }
    rows.append((
        "serving/overload_goodput_2x",
        g2b / max(g2u, 1e-9) * 100,
        f"bounded {g2b:.0f} vs unbounded {g2u:.0f} goodput tok/s at 2x "
        f"capacity (shed {points['x2']['bounded']['shed_rate']:.0%}, "
        f"preempt {points['x2']['bounded']['preempt_rate']:.2f}/req); "
        f"analytic shed ordering matches: {order_ok}"))
    rows.append((
        "serving/overload_shed_3x",
        meas_shed[-1] * 100,
        f"bounded shed rate across x1/x2/x3 capacity: "
        f"{meas_shed} (predicted {pred_shed}); unbounded p95 latency "
        f"{points[f'x{OV_MULTIPLES[-1]:g}']['unbounded']['p95_latency_s']}s vs bounded "
        f"{points[f'x{OV_MULTIPLES[-1]:g}']['bounded']['p95_latency_s']}s at 3x"))


_SWEEPS = ("megastep", "mixed", "precision", "kv", "kernels", "async",
           "paging", "overload")


def run(sweeps: Sequence[str] = _SWEEPS) -> List[Tuple[str, float, str]]:
    cfg, model, params = _build_model()
    path = pathlib.Path(__file__).resolve().parents[1] / \
        "BENCH_serving.json"
    # merge into the existing file so a single-sweep run never clobbers
    # the other sections' numbers
    out = json.loads(path.read_text()) if path.exists() else {}
    rows: List[Tuple[str, float, str]] = []
    if "megastep" in sweeps:
        _sweep_megastep(cfg, model, params, out, rows)
    if "mixed" in sweeps:
        _sweep_mixed(cfg, model, params, out, rows)
    if "precision" in sweeps:
        _sweep_precision(cfg, model, params, out, rows)
    if "kv" in sweeps:
        _sweep_kv(cfg, model, params, out, rows)
    if "kernels" in sweeps:
        _sweep_kernels(cfg, model, params, out, rows)
    if "async" in sweeps:
        _sweep_async(cfg, model, params, out, rows)
    if "paging" in sweeps:
        _sweep_paging(cfg, model, params, out, rows)
    if "overload" in sweeps:
        _sweep_overload(cfg, model, params, out, rows)
    path.write_text(json.dumps(out, indent=2) + "\n")
    rows.append(("serving/bench_json", 0.0,
                 f"wrote {path.name} sections: {', '.join(sweeps)}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sweep", default="all",
                    choices=list(_SWEEPS) + ["all"],
                    help="which sweep to run (default: all)")
    args = ap.parse_args()
    enable_compile_cache()
    sweeps = _SWEEPS if args.sweep == "all" else (args.sweep,)
    print("name,us_per_call,derived")
    for name, us, derived in run(sweeps):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
