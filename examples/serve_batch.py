"""Batched serving example (the paper's decode workload, deployed):
load (or train-then-quantize) a small model and serve a stream of
requests through the continuous-batching engine at Q8/Q4 — the paper's
precision sweep as a deployment decision.

  PYTHONPATH=src python examples/serve_batch.py --quant q4_0

With ``--frontend`` the same stream runs through the asyncio actor
front-end (``repro.launch.serve.AsyncServingFrontend``) instead of the
blocking ``engine.run()`` — the deployment shape for interactive
serving. The front-end API in one screen::

    fe = AsyncServingFrontend(engine, max_pending=8)

    # await the full greedy/sampled completion
    toks = await fe.generate(prompt, max_new_tokens=24)

    # stream tokens as megastep blocks drain; enforce a deadline —
    # on expiry generate() raises DeadlineExceeded carrying the
    # partial tokens, and the request's slot retires in the engine
    # via the frozen-write cancel path
    try:
        toks = await fe.generate(prompt, max_new_tokens=24,
                                 deadline_s=0.5,
                                 on_token=lambda t: print(t, end=" "),
                                 temperature=0.7, top_k=40)
    except DeadlineExceeded as e:
        partial = e.tokens

    await fe.close()        # drain staged work, stop the serve loop

One coroutine owns the engine, so ``generate`` is safe to call from
any number of concurrent tasks; ``max_pending`` bounds how many
admitted-but-unfinished requests exist at once (further ``generate``
calls suspend — backpressure, not an error). Cancelling the awaiting
task (``task.cancel()``) cancels the request in the engine too.

  PYTHONPATH=src python examples/serve_batch.py --frontend --deadline-s 2

Failure semantics (the overload-PR contract — every outcome is typed
and observable; overload is a steady state, not a crash)::

    # shed at admission, before holding any resource: subclasses of
    # serving.SubmitReject (a ValueError)
    try:
        engine.submit(req)
    except QueueFull as e:          # max_queue bound hit
        sleep(e.retry_after_s or 0.1); resubmit()
    except InfeasibleDeadline:      # deadline < service even unqueued
        drop()                      # no tokens it could ever use
    except PromptTooLong:           # can never fit the cache
        truncate_or_raise_max_len()

    # preempted under pool pressure: evicted, requeued, resumed by
    # re-prefilling prompt + generated prefix — token-identical under
    # greedy sampling; req.preemptions counts evictions
    # poisoned (NaN/inf logits): the slot freezes its cache and
    # retires with req.error == "nonfinite-logits"; co-batched
    # requests' streams are untouched (byte-identical)

    # the asyncio front-end surfaces the same outcomes per call:
    # Backpressure (with retry_after_s) for QueueFull, RequestFailed
    # for error-retired requests, DeadlineExceeded (partial tokens)
    # for expired deadlines, ValueError for the other rejects

``engine.audit()`` (or ``launch.serve --audit``, per step) asserts the
block-pool/queue/slot invariants; ``serving.FaultInjector`` replays
seeded fault schedules against all of the above deterministically.
"""
import argparse
import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.serve import AsyncServingFrontend, DeadlineExceeded
from repro.models import Model
from repro.serving import Request, SamplingConfig, ServingEngine
import dataclasses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quant", "--precision", dest="quant",
                    default="q8_0", choices=["bf16", "q8_0", "q4_0"],
                    help="serving weight precision (paper §5.3; "
                         "--precision kept as a back-compat alias)")
    ap.add_argument("--kv-quant", dest="kv_quant", default="bf16",
                    choices=["bf16", "q8_0", "q4_0"],
                    help="KV-cache precision: groupwise int8 payload + "
                         "scales per ring-buffer position (the decode "
                         "stream that grows with context; no-op for "
                         "recurrent families)")
    # When does paging pay? When requests share prompt prefixes (a
    # system prompt, few-shot examples): the prefix cache maps the
    # shared pages into every hitting slot and skips re-prefilling
    # them. And when the dense slots*max_len prealloc overshoots what
    # is actually live: the pool only holds pages in use. It costs a
    # per-step block-table gather, so for short-context streams with
    # no reuse, dense (--page-size 0) is the right default.
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV-cache page size in tokens; 0 = dense "
                         "(see note above on when paging pays)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix pages across requests "
                         "(the example stream reuses a common prefix "
                         "so hits actually occur)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--frontend", action="store_true",
                    help="serve through the asyncio front-end "
                         "(streaming callbacks, deadlines) instead of "
                         "engine.run()")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline for --frontend")
    args = ap.parse_args()

    cfg = reduced(get_config("mistral-nemo-12b"), num_layers=4,
                  d_model=256, d_ff=512)
    model_cfg = dataclasses.replace(cfg, quant_policy=args.quant,
                                    kv_quant=args.kv_quant)
    model = Model(model_cfg)
    params = model.init(jax.random.PRNGKey(0), quantize=False)
    if args.quant != "bf16":
        print(f"serving with {args.quant} weights "
              f"(paper: Q4 = 4.5 bits/weight)")
    if args.kv_quant != "bf16":
        print(f"serving with a {args.kv_quant} KV cache "
              f"(cache bytes x {8.5 / 16 if args.kv_quant == 'q8_0' else 4.5 / 16:.3f})")

    # the engine quantizes the weight pytree on entry per quant_policy;
    # kv_quant stores cache leaves as int8 payload + groupwise scales
    engine = ServingEngine(model, params, slots=args.slots, max_len=256,
                           sampling=SamplingConfig(temperature=0.7,
                                                   top_k=40),
                           quant_policy=args.quant,
                           kv_quant=args.kv_quant,
                           page_size=args.page_size,
                           prefix_cache=args.prefix_cache)
    rng = np.random.default_rng(0)
    # with --prefix-cache, every request opens with the same "system
    # prompt" so the shared pages actually hit; tails stay unique
    shared = (rng.integers(1, cfg.vocab_size, size=2 * args.page_size
                           + 1).astype(np.int32)
              if args.prefix_cache and args.page_size else
              np.zeros(0, np.int32))
    prompts = [np.concatenate([
                   shared,
                   rng.integers(1, cfg.vocab_size,
                                size=5 + i % 4).astype(np.int32)])
               for i in range(args.requests)]

    t0 = time.time()
    if args.frontend:
        async def drive():
            fe = AsyncServingFrontend(engine,
                                      max_pending=2 * args.slots)

            async def one(p):
                try:
                    return await fe.generate(
                        p, max_new_tokens=args.max_new,
                        deadline_s=args.deadline_s,
                        temperature=0.7, top_k=40)
                except DeadlineExceeded as e:
                    return e            # keep the partial tokens
            outs = await asyncio.gather(*[one(p) for p in prompts])
            await fe.close()
            return outs

        outs = asyncio.run(drive())
        dt = time.time() - t0
        expired = sum(isinstance(o, DeadlineExceeded) for o in outs)
        first = next((o for o in outs
                      if not isinstance(o, DeadlineExceeded)), [])
        print(f"{len(outs) - expired}/{len(outs)} requests done, "
              f"{expired} deadline-expired, "
              f"{engine.stats.tokens_generated} tokens in {dt:.1f}s "
              f"({engine.stats.tokens_generated / dt:.1f} tok/s)")
        print("sample:", list(first)[:12])
        return

    reqs = [Request(uid=i, prompt=p, max_new_tokens=args.max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    dt = time.time() - t0
    done = sum(r.done for r in reqs)
    print(f"{done}/{len(reqs)} requests done, "
          f"{engine.stats.tokens_generated} tokens in {dt:.1f}s "
          f"({engine.stats.tokens_generated / dt:.1f} tok/s, "
          f"{engine.stats.steps} batched decode steps)")
    if engine.page_size:
        print(f"paging: {engine.cache_blocks} blocks x "
              f"{engine.page_size} tokens, {engine.stats.prefix_hits} "
              f"prefix hits ({engine.stats.prefix_hit_tokens} prompt "
              f"tokens skipped)")
    print("sample:", reqs[0].output[:12])


if __name__ == "__main__":
    main()
