"""Serving launcher + actor-style asyncio front-end.

Two layers live here:

``AsyncServingFrontend``
    An asyncio actor wrapped around :class:`ServingEngine`. One
    background coroutine owns the engine and alternates between
    ``engine.step()`` (run in a thread-pool executor so the event
    loop stays live while the device computes) and a between-steps
    housekeeping pass that admits newly submitted requests, enforces
    per-request deadlines via ``engine.cancel`` (the frozen-write
    retirement path — the slot goes PHASE_IDLE and any in-flight
    megastep leaves its cache untouched), and streams freshly drained
    tokens to per-request callbacks.  All engine mutation happens on
    that one coroutine, so no locking is needed; ``generate()``
    merely stages work and awaits a future.  A semaphore bounds the
    number of admitted-but-unfinished requests (backpressure): when
    ``max_pending`` requests are in flight, new ``generate()`` calls
    suspend until a slot of the bound frees up.

CLI (``main``)
    Brings up the engine on a reduced (or full) model and runs a
    synthetic request stream, either synchronously or — with
    ``--frontend`` — through the asyncio front-end with staggered
    arrivals and optional deadlines.  Reported tok/s excludes jit
    compile: a warmup request pays compilation, ``engine.reset()``
    clears the stats (compiled executables survive), and the timed
    run reports decode tok/s from ``EngineStats.decode_wall_s`` with
    the warmup/compile split printed separately.

  PYTHONPATH=src python -m repro.launch.serve --arch mistral-nemo-12b \
      --no-reduced --requests 8 --precision q8_0 --pipeline-depth 2
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.quant import quantize_tree
from repro.serving import (QueueFull, Request, SamplingConfig,
                           ServingEngine, SubmitReject)


class DeadlineExceeded(Exception):
    """Raised by ``AsyncServingFrontend.generate`` when a request's
    deadline expires before it finishes. ``.tokens`` carries the
    partial output generated before cancellation."""

    def __init__(self, uid: int, tokens: List[int]):
        super().__init__(
            f"request {uid} cancelled at deadline after "
            f"{len(tokens)} token(s)")
        self.uid = uid
        self.tokens = tokens


class Backpressure(Exception):
    """Raised by ``generate`` when the engine sheds the request at its
    queue bound (``QueueFull``). ``retry_after_s`` is the predicted
    backlog drain time — from the engine's measured substep rate when
    it has one, else the front-end's ``drain_hint_s`` (seeded from
    ``dispatch.plan``'s predicted decode rate) scaled by queue depth.
    Callers should back off ~that long before resubmitting."""

    def __init__(self, uid: int, retry_after_s: Optional[float],
                 queue_depth: int):
        hint = (f"retry after ~{retry_after_s:.3f}s"
                if retry_after_s is not None else "retry after a drain")
        super().__init__(
            f"request {uid} shed at queue bound "
            f"(depth {queue_depth}); {hint}")
        self.uid = uid
        self.retry_after_s = retry_after_s
        self.queue_depth = queue_depth


class RequestFailed(Exception):
    """Raised by ``generate`` when the engine error-retires the
    request (e.g. ``nonfinite-logits`` from the in-scan finiteness
    check). ``.tokens`` carries output generated before the failure;
    co-batched requests are unaffected."""

    def __init__(self, uid: int, reason: str, tokens: List[int]):
        super().__init__(
            f"request {uid} failed: {reason} "
            f"(after {len(tokens)} token(s))")
        self.uid = uid
        self.reason = reason
        self.tokens = tokens


@dataclasses.dataclass
class _Handle:
    req: Request
    future: asyncio.Future
    on_token: Optional[Callable[[int], None]]
    deadline: Optional[float]        # absolute time.monotonic() deadline
    sent: int = 0                    # tokens already streamed
    admitted: bool = False           # engine.submit() has run
    expired: bool = False            # cancelled by the deadline sweep


class AsyncServingFrontend:
    """Actor-style asyncio front-end over a :class:`ServingEngine`.

    Usage::

        fe = AsyncServingFrontend(engine, max_pending=32)
        toks = await fe.generate(prompt, max_new_tokens=16,
                                 deadline_s=0.5, on_token=print)
        await fe.close()

    ``generate`` resolves with the full token list, raises
    :class:`DeadlineExceeded` (carrying partial tokens) on deadline
    expiry, raises :class:`Backpressure` (with a retry-after hint)
    when the engine sheds the request at its ``max_queue`` bound,
    raises :class:`RequestFailed` when the engine error-retires it
    (nonfinite logits), and propagates ``ValueError`` for requests the
    engine rejects at ``submit()`` (empty prompt, negative budget,
    ``PromptTooLong``). Cancelling the awaiting asyncio task cancels
    the request in the engine too — the slot retires via the same
    frozen-write path.

    ``drain_hint_s`` seeds the backpressure retry-after estimate (per
    queued request) before the engine has measured its own substep
    rate — pass ``dispatch.plan``'s predicted seconds-per-request.
    """

    def __init__(self, engine: ServingEngine, *, max_pending: int = 32,
                 drain_hint_s: Optional[float] = None):
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1 (got {max_pending})")
        self.engine = engine
        self.max_pending = max_pending
        self.drain_hint_s = drain_hint_s
        self._sem = asyncio.Semaphore(max_pending)
        self._incoming: List[_Handle] = []   # staged, not yet submitted
        self._live: List[_Handle] = []       # submitted, future pending
        self._to_cancel: List[_Handle] = []  # staged explicit cancels
        self._wake: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._closed = False
        self._uid = 0

    # -- public API ---------------------------------------------------

    async def generate(self, prompt, *, max_new_tokens: int = 32,
                       eos_id: int = -1,
                       temperature: Optional[float] = None,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       deadline_s: Optional[float] = None,
                       on_token: Optional[Callable[[int], None]] = None,
                       ) -> List[int]:
        if self._closed:
            raise RuntimeError("front-end is closed")
        await self._sem.acquire()        # backpressure bound
        loop = asyncio.get_running_loop()
        self._ensure_loop()
        self._uid += 1
        req = Request(uid=self._uid,
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      temperature=temperature, top_k=top_k, top_p=top_p)
        handle = _Handle(
            req=req, future=loop.create_future(), on_token=on_token,
            deadline=(time.monotonic() + deadline_s
                      if deadline_s is not None else None))
        self._incoming.append(handle)
        self._wake.set()
        try:
            return await handle.future
        except asyncio.CancelledError:
            # caller bailed: retire the request's slot between steps
            self._to_cancel.append(handle)
            self._wake.set()
            raise

    async def close(self) -> None:
        """Stop the serve loop once staged work has drained."""
        self._closed = True
        if self._wake is not None:
            self._wake.set()
        if self._task is not None:
            await self._task
            self._task = None

    # -- serve loop ---------------------------------------------------

    def _ensure_loop(self) -> None:
        if self._wake is None:
            self._wake = asyncio.Event()
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(
                self._serve())

    def _admit_incoming(self) -> None:
        staged, self._incoming = self._incoming, []
        for h in staged:
            try:
                self.engine.submit(h.req)
                h.admitted = True
                self._live.append(h)
            except QueueFull as e:       # shed: surface backpressure
                retry = e.retry_after_s
                if retry is None and self.drain_hint_s is not None:
                    retry = self.drain_hint_s * max(e.queue_depth, 1)
                if not h.future.done():
                    h.future.set_exception(Backpressure(
                        h.req.uid, retry, e.queue_depth))
                self._sem.release()
            except ValueError as e:      # rejected at admission
                if not h.future.done():
                    h.future.set_exception(e)
                self._sem.release()

    def _sweep_cancellations(self) -> None:
        staged, self._to_cancel = self._to_cancel, []
        for h in staged:
            self.engine.cancel(h.req)
        now = time.monotonic()
        for h in self._live:
            if (h.deadline is not None and now >= h.deadline
                    and not h.req.done):
                h.expired = True
                self.engine.cancel(h.req)

    def _publish(self) -> None:
        still = []
        for h in self._live:
            fresh = h.req.output[h.sent:]
            h.sent += len(fresh)
            if h.on_token is not None:
                for tok in fresh:
                    h.on_token(tok)
            if not h.req.done:
                still.append(h)
                continue
            if not h.future.done():
                if h.expired:
                    h.future.set_exception(DeadlineExceeded(
                        h.req.uid, list(h.req.output)))
                elif h.req.error is not None:
                    h.future.set_exception(RequestFailed(
                        h.req.uid, h.req.error, list(h.req.output)))
                else:
                    h.future.set_result(list(h.req.output))
            self._sem.release()
        self._live = still

    async def _serve(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            self._admit_incoming()
            self._sweep_cancellations()
            self._publish()
            if not self.engine.has_work() and not self._live:
                if self._closed and not self._incoming:
                    return
                self._wake.clear()
                if not self._incoming and not self._to_cancel:
                    await self._wake.wait()
                continue
            # the event loop stays live while the engine steps: new
            # generate() calls stage work that the next iteration of
            # this loop admits between steps.
            await loop.run_in_executor(None, self.engine.step)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="shrink the config for smoke runs "
                         "(--no-reduced for the paper-size model)")
    ap.add_argument("--precision", default="bf16",
                    choices=["bf16", "q8_0", "q4_0"])
    ap.add_argument("--kv-quant", dest="kv_quant", default="bf16",
                    choices=["bf16", "q8_0", "q4_0"],
                    help="KV-cache precision: groupwise int8 payload + "
                         "scale leaves per cached position (no-op for "
                         "ssm/hybrid state)")
    ap.add_argument("--kernels", default="",
                    choices=["", "xla", "pallas"],
                    help="kernel backend for quantized decode GEMVs + "
                         "quantized-cache attention reads (default: "
                         "derive from the config's use_pallas)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--megastep-k", type=int, default=None,
                    help="decode tokens per fused dispatch "
                         "(default: engine's DEFAULT_MEGASTEP_K)")
    ap.add_argument("--admission", default="chunked",
                    choices=["chunked", "stall"],
                    help="prompt admission: ride inside the megastep "
                         "scan (chunked) or batched prefill dispatches "
                         "between megasteps (stall)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="on-device prompt chunk size for chunked "
                         "admission (default: max(megastep_k, 16))")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable cache/SlotState buffer donation into "
                         "the megastep (doubles carry HBM traffic)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="megasteps kept in flight: 1 = serial "
                         "dispatch/drain, 2 = double-buffered (drain N "
                         "overlaps device megastep N+1)")
    # Paging pays when traffic shares prompt prefixes (the prefix
    # cache skips re-prefilling shared pages) or when the dense
    # slots*max_len prealloc overshoots live tokens; it costs a
    # per-step gather of the block table, so leave it off for
    # short-context, no-reuse streams. dispatch.plan's page_size knob
    # (fed by scheduler.simulate_paging) makes the same call
    # analytically.
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV-cache page size in tokens; 0 = dense "
                         "slot-major cache (no paging)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="reuse full prompt-prefix pages across "
                         "requests via content hashing (requires "
                         "--page-size > 0 and chunked admission)")
    ap.add_argument("--frontend", action="store_true",
                    help="route the synthetic stream through the "
                         "asyncio front-end (staggered arrivals, "
                         "streaming callbacks) instead of engine.run()")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline for --frontend runs")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the engine's admission queue: submit() "
                         "past this depth raises QueueFull (load "
                         "shedding; the front-end surfaces it as "
                         "Backpressure with a retry-after hint). "
                         "0 = unbounded")
    ap.add_argument("--audit", action="store_true",
                    help="run engine.audit() after every step (block-"
                         "pool partition, refcounts, slot/queue "
                         "invariants) — cheap host-side checks; raises "
                         "EngineAuditError on the first violation")
    return ap


def _make_requests(cfg, n: int, max_new: int,
                   shared_prefix: int = 0) -> List[Request]:
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size,
                          size=shared_prefix).astype(np.int32)
    return [Request(uid=i,
                    prompt=np.concatenate([shared, rng.integers(
                        1, cfg.vocab_size,
                        size=4 + i % 5).astype(np.int32)]),
                    max_new_tokens=max_new)
            for i in range(n)]


def _run_frontend(engine: ServingEngine, cfg, args) -> int:
    """Drive the synthetic stream through the asyncio front-end.
    Returns the number of deadline-expired requests."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=4 + i % 5)
               .astype(np.int32) for i in range(args.requests)]

    async def drive():
        fe = AsyncServingFrontend(engine,
                                  max_pending=max(2 * args.slots, 4))

        async def one(p):
            try:
                await fe.generate(p, max_new_tokens=args.max_new,
                                  deadline_s=args.deadline_s)
                return 0
            except DeadlineExceeded:
                return 1
            except (Backpressure, RequestFailed):
                return 1                 # shed or error-retired

        tasks = []
        for p in prompts:
            tasks.append(asyncio.ensure_future(one(p)))
            await asyncio.sleep(0)       # staggered arrivals
        expired = sum(await asyncio.gather(*tasks))
        await fe.close()
        return expired

    return asyncio.run(drive())


def build_engine(args) -> ServingEngine:
    """The engine ``main`` serves, from parsed CLI arguments: random
    weights from a fixed seed, quantized per ``--precision``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(cfg, quant_policy=args.precision,
                              kv_quant=args.kv_quant)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0), quantize=False)
    if args.precision != "bf16":
        params = quantize_tree(params, args.precision)

    engine = ServingEngine(model, params, slots=args.slots,
                           max_len=args.max_len,
                           sampling=SamplingConfig(temperature=0.8,
                                                   top_k=40),
                           megastep_k=args.megastep_k,
                           admission=args.admission,
                           prefill_chunk=args.prefill_chunk,
                           donate_carries=not args.no_donate,
                           kernels=args.kernels or None,
                           pipeline_depth=args.pipeline_depth,
                           page_size=args.page_size,
                           prefix_cache=args.prefix_cache,
                           max_queue=args.max_queue)
    engine.audit_every_step = args.audit
    return engine


def main(argv: Optional[List[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    engine = build_engine(args)
    cfg = engine.cfg

    # Warmup pays jit compile; reset() keeps the compiled executables
    # but zeroes the stats so the timed run is compile-excluded (the
    # ROADMAP bench-methodology note: never fold compile into tok/s).
    t0 = time.perf_counter()
    engine.submit(Request(uid=-1,
                          prompt=np.arange(1, 6, dtype=np.int32),
                          max_new_tokens=max(args.max_new, 1)))
    engine.run()
    warmup_s = time.perf_counter() - t0
    engine.reset()

    t0 = time.perf_counter()
    expired = 0
    if args.frontend:
        expired = _run_frontend(engine, cfg, args)
    else:
        # with --prefix-cache, open every prompt with the same "system
        # prompt" (2 full pages + 1) so the shared pages actually hit
        shared = (2 * args.page_size + 1
                  if args.prefix_cache and args.page_size else 0)
        for r in _make_requests(cfg, args.requests, args.max_new,
                                shared_prefix=shared):
            try:
                engine.submit(r)
            except SubmitReject:
                # typed shed (QueueFull under --max-queue): counted in
                # stats.shed and reported below, never fatal — the CLI
                # submits its whole batch upfront, so a bounded queue
                # legitimately refuses the overflow
                pass
        engine.run()
    wall = time.perf_counter() - t0

    st = engine.stats
    decode_s = max(st.decode_wall_s, 1e-9)
    admit = (f"{st.inscan_admissions} in-scan admissions, "
             f"{st.chunk_refills} chunk refills"
             if engine.admission == "chunked" else
             f"{st.prefill_batches} prefill batches")
    print(f"arch={cfg.name} precision={args.precision} "
          f"kv_quant={engine.kv_quant} kernels={engine.kernels} "
          f"admission={engine.admission} depth={engine.pipeline_depth} "
          f"page_size={engine.page_size}: "
          f"{st.tokens_generated} tokens / {decode_s:.2f}s decode = "
          f"{st.tokens_generated / decode_s:.1f} tok/s "
          f"(warmup+compile {warmup_s:.1f}s excluded; run wall "
          f"{wall:.2f}s; {st.steps} decode steps in "
          f"{st.megasteps} dispatches [K={engine.megastep_k}], "
          f"{st.prefills} prefills: {admit}; "
          f"drain-wait {st.drain_wait_s:.3f}s)")
    if st.shed or st.preemptions or st.poisoned:
        print(f"overload: {st.shed} shed, {st.preemptions} "
              f"preemptions, {st.poisoned} poisoned-retired "
              f"(queue bound {engine.max_queue or 'none'})")
    if engine.page_size:
        print(f"paging: {engine.cache_blocks} blocks x "
              f"{engine.page_size} tokens, {engine.blocks_in_use} "
              f"blocks live after drain, {st.prefix_hits} prefix "
              f"hits ({st.prefix_hit_tokens} prompt tokens skipped)")
    if args.frontend:
        print(f"frontend: {args.requests - expired} completed, "
              f"{expired} deadline-expired, "
              f"{st.cancelled} engine cancellations")


if __name__ == "__main__":
    main()
