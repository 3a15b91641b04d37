"""Continuous-batching serving engine: chunked prefill admission,
per-slot sampling, and a pipelined device-resident multi-token decode
"megastep" loop with donated carries.

The engine owns a fixed-size decode batch (``slots``). Requests queue
up, and every ``step()`` runs one **megastep**: ``megastep_k`` decode
iterations fused into a single jitted ``jax.lax.scan`` that threads
(cache, SlotState) on device and returns a ``(3, K, slots)`` block of
(tokens, emission mask, prefill progress) — one dispatch and one
device→host transfer per K tokens instead of per token.

**Pipelined dispatch/drain** (``pipeline_depth``): megastep dispatch
is asynchronous under JAX, so ``step()`` is split into a dispatch half
(stage the admission arrays from the host's current slot view, launch
megastep N+1) and a drain half (block on megastep N's packed token
block — the loop's ONE synchronization point, ``np.asarray(block)``).
With ``pipeline_depth=1`` the two halves run back-to-back (the serial
PR-1/2 loop: the device idles while the host unpacks K×slots tokens
and builds the next admission arrays). With ``pipeline_depth=2``
exactly one megastep stays in flight: while the device runs N+1, the
host drains N and stages N+2's admissions — the host-side gap between
device steps (the paper's §5 dispatch-overhead story, on our side of
the fence) is hidden up to the device-step time.

Why token identity survives pipelining: slots are independent, and the
host's view of slot state is allowed to go stale by one megastep.
Admissions staged while N is in flight target N+1's slot view —
a slot the host believes free was already idle (frozen cache, no
emission) throughout N, and a slot retired *inside* N keeps emitting
nothing under the frozen write mask until the host drains N and
observes it. Each in-flight block carries a snapshot of its slot
occupants at dispatch time, so drained tokens are attributed to the
request that actually rode that megastep, and the host prompt-cursor
mirror is only advanced from blocks whose occupant is still the live
request. The per-request token streams are therefore byte-identical
to the serial engine's (the property suite pins depth>1 == depth 1
across every cache family, admission mode and K); only *latency*
moves — a slot freed inside N is refilled at N+2 instead of N+1, and
one trailing all-idle megastep is dispatched per queue drain.

Why: the paper's §5 headline (2-thread CPU 17 tok/s beats the GPU's
12.8 at batch-1 decode) is a *dispatch-overhead* result, not a FLOPs
result — the GPU loses because every token pays kernel-launch/encode
and a CPU↔GPU sync. The megastep amortizes that fixed cost K×, and the
three mechanisms below keep *mixed* prefill/decode traffic — where
"Understanding LLMs in Your Pockets" (arXiv:2410.03613) shows
on-device throughput actually collapses — on the same amortized path:

- **Chunked prefill admission** (``admission="chunked"``, the
  default): prompts ride *inside* the megastep scan. Each slot carries
  a ``phase`` (idle / prefill / decode) plus a ``prefill_pos`` cursor
  and a fixed-size on-device prompt chunk buffer (``prefill_chunk``
  tokens, refreshed by the host between megasteps through the same
  megastep dispatch — zero extra host dispatches). A prefilling slot
  consumes one prompt token per scan substep through
  ``Model.decode_step`` — the same cache-write path decode uses, so
  the existing ``advance_mask`` machinery covers admission for every
  cache family — and emits its first sampled token the substep it
  consumes the last prompt token. Decoding neighbours never stall.
  ``admission="stall"`` keeps the PR-1 behaviour: length-bucketed
  batched prefill dispatches between megasteps, which stall every
  decoding slot meanwhile.
- **Per-slot sampling params**: ``temperature`` / ``top_k`` /
  ``top_p`` are SlotState fields threaded through the scanned
  ``sample_batched``, so heterogeneous requests (greedy next to
  temperature 1.2) share one batch; greedy rows stay exact argmax and
  consume no randomness.
- **Donated megastep carries** (``donate_carries=True``): the cache +
  SlotState pytrees are donated into the megastep and prefill jits
  (``donate_argnums``), so XLA updates the multi-MB KV/state carry in
  place instead of writing a second copy — halving the carry's HBM
  traffic at each dispatch boundary.

EOS/length retirement stays in-scan via the length-frozen cache write
mask (``decode_step(advance_mask=...)``), so finished slots emit pad
tokens without corrupting their cache.

**Failure semantics (overload + poisoned requests).** On-device
serving lives permanently near its resource ceiling, so running out is
a steady state to schedule around, not an error to crash on. Three
distinct outcomes, all observable per request:

- **Shed** — ``submit()`` raises a typed reject *before* the request
  holds any resource: ``QueueFull`` when ``max_queue`` is set and the
  queue is at its bound (carries a ``retry_after_s`` hint from the
  engine's measured drain rate), ``InfeasibleDeadline`` when
  ``Request.deadline_s`` cannot be met even by an empty engine, and
  ``PromptTooLong`` when the prompt can never fit the cache (the
  ring/page write would otherwise corrupt the slot's own stream).
  All subclass ``SubmitReject`` (a ``ValueError``). The queue orders
  by earliest deadline first (EDF); deadline-less requests stay FIFO
  behind their submission order.
- **Preempted** — when a paged admission cannot get blocks even after
  registry eviction, the engine may preempt a victim slot (least
  progress, non-shared-prefix first, and only one whose EDF key is
  strictly later than the incoming request's — so preemption can
  never livelock). The victim's slot retires through the frozen-write
  mask, its private blocks are recycled refcount-aware, and the
  request is requeued to recompute from its prompt + generated
  prefix; a greedy preempted-then-resumed request is token-identical
  to an uninterrupted run. ``Request.preemptions`` counts round
  trips; the outcome is otherwise invisible to the caller.
- **Errored** — an in-jit finiteness check on per-slot logits retires
  any slot that produces NaN/inf through the same frozen-write path
  (``Request.error = "nonfinite-logits"``, ``done=True``) while the
  rest of the batch continues untouched; survivors are byte-identical
  to a run without the poisoned request.

``audit()`` checks the allocator invariants (free ∪ quarantined ∪
referenced partitions the pool; refcounts match table references;
block 0 never mapped) after any step — ``serving.faults`` runs it
after every step under chaos schedules, ``launch.serve --audit`` in
production loops.

**Tracing.** The serving loop is always instrumented with
``jax.profiler.TraceAnnotation`` host spans, on the same clock as the
device's ops in a profiler trace; with no profiler running each costs
under a microsecond and records nothing. Per megastep ``N``:
``engine.stage`` (admission arrays; ``admitted``), ``engine.dispatch``
(the jitted megastep call; ``megastep=N``), ``engine.drain_wait``
(blocked on block ``N``'s transfer; ``megastep=N``) and
``engine.drain`` (``emitted``, prompt tokens ``fed``, and the live
cache positions the substeps' attention read, ``attended``, summed
over substeps and slots); at
``pipeline_depth`` 1 they run in that order, at depth 2 megastep
``N + 1``'s stage and dispatch come before ``N``'s drain wait. Per
request: ``engine.submit`` (``uid``), ``engine.admit`` (``uid``,
``queued_us`` since it last entered the queue) and
``engine.first_token`` (``uid``, ``prefill_us`` from admission,
``ttft_us`` from submit); ``Request.t_submit``/``t_admit``/
``t_first_token`` keep the same stamps. The asyncio front end adds
``frontend.step``, ``frontend.admit``, ``frontend.cancel`` and
``frontend.publish`` (tokens streamed, ``n``). On the device, ``lm_head`` and ``sampler`` are
named scopes, and the Pallas kernels are named ``quant_matmul``,
``decode_attention`` and ``decode_attention_quant``. To capture them
around a serving loop::

    jax.profiler.start_trace("serve-trace")
    ...  # serve
    jax.profiler.stop_trace()

then open the ``.xplane.pb`` under ``plugins/profile/`` in TensorBoard
or XProf, or read it with ``jax.profiler.ProfileData.from_file``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Model
from repro.quant.quantize import QuantizedTensor, quantize_tree
from repro.serving.sampler import SamplingConfig, sample, sample_batched

# K when the caller gives none: one dispatch per 8 tokens keeps
# Python/XLA launch overhead ≲10% for even the smallest models we serve.
DEFAULT_MEGASTEP_K = 8

PAD_ID = 0

# SlotState.phase values (device-resident slot lifecycle)
PHASE_IDLE = 0      # retired / never filled: cache frozen, no emission
PHASE_PREFILL = 1   # consuming prompt tokens in-scan, no emission yet
PHASE_DECODE = 2    # generating: sample + emit every substep

_INF = float("inf")
_GREEDY = SamplingConfig()


@contextlib.contextmanager
def _timed(stats, field: str, name: str, **args):
    """A host span ``name`` (see the module docstring's Tracing
    section) whose wall seconds are also added to ``stats.<field>``:
    one instrumentation point per boundary, for the counter and the
    span alike. Yields the span, for stats known only at its end
    (``span.set_metadata(...)``)."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **args) as span:
        yield span
    setattr(stats, field, getattr(stats, field) + time.perf_counter() - t0)


class SubmitReject(ValueError):
    """Typed admission reject: the request was refused at ``submit()``
    before holding any engine resource. ``retry_after_s`` is a hint
    (None when the engine has no measured rate yet); ``reason`` names
    the reject class for logging/metrics."""
    reason = "rejected"

    def __init__(self, msg: str, *, uid: Optional[int] = None,
                 retry_after_s: Optional[float] = None,
                 queue_depth: int = 0):
        super().__init__(msg)
        self.uid = uid
        self.retry_after_s = retry_after_s
        self.queue_depth = queue_depth


class QueueFull(SubmitReject):
    """Load shed: the bounded queue (``max_queue``) is at capacity."""
    reason = "queue_full"


class InfeasibleDeadline(SubmitReject):
    """Load shed: ``Request.deadline_s`` cannot be met even if the
    request were admitted immediately (measured service rate)."""
    reason = "infeasible_deadline"


class PromptTooLong(SubmitReject):
    """The prompt can never fit this engine's cache: admitting it
    would write past the slot's rows and corrupt its own stream."""
    reason = "prompt_too_long"


class EngineAuditError(AssertionError):
    """An allocator/scheduler invariant does not hold (see audit())."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 32
    eos_id: int = -1                 # -1 → never stops early
    # per-request sampling overrides (None → engine's SamplingConfig)
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    # soft completion deadline in seconds from submit; orders the queue
    # (EDF) and arms the infeasibility shed — the engine never cancels
    # on expiry itself (the front-end's deadline sweep does that)
    deadline_s: Optional[float] = None
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False          # retired via ServingEngine.cancel()
    error: Optional[str] = None      # fault status (e.g. poisoned logits)
    preemptions: int = 0             # times evicted + requeued for recompute
    # host clock (time.perf_counter) stamps: submit(), the latest
    # placement in a slot, and the first output token drained
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None
    # scheduler-internal: submission order + absolute deadline
    _seq: int = dataclasses.field(default=0, repr=False)
    _deadline_abs: Optional[float] = dataclasses.field(default=None,
                                                       repr=False)
    _t_queued: float = dataclasses.field(default=0.0, repr=False)


@dataclasses.dataclass
class EngineStats:
    steps: int = 0               # decode substeps executed (K per megastep)
    megasteps: int = 0           # fused decode dispatches
    tokens_generated: int = 0
    prefills: int = 0            # requests admitted (either path)
    prefill_batches: int = 0     # stall-path prefill dispatches
    inscan_admissions: int = 0   # requests admitted inside the megastep
    chunk_refills: int = 0       # prompt chunk buffers refreshed
    cancelled: int = 0           # requests retired via cancel()
    prefix_hits: int = 0         # admissions that reused cached blocks
    prefix_hit_tokens: int = 0   # prompt tokens skipped via shared pages
    blocks_recycled: int = 0     # pool blocks returned to the free list
    preemptions: int = 0         # slots evicted + requeued for recompute
    shed: int = 0                # submits rejected (queue full / deadline)
    poisoned: int = 0            # requests retired on non-finite logits
    prompt_tokens_fed: int = 0   # prompt tokens run through the model
                                 # (prefix-cache hits are not fed)
    decode_wall_s: float = 0.0   # wall time in megastep dispatch + drain
    # pipelining attribution: where the decode wall actually goes
    stage_wall_s: float = 0.0    # host time building admission arrays
    drain_wait_s: float = 0.0    # host blocked on the device→host block
                                 # transfer (shrinks when pipelining
                                 # overlaps drain N with megastep N+1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SlotState:
    """Device-resident per-slot serving state threaded through the
    megastep scan. Mirrors the host's ``active``/``Request`` view; the
    host only touches it between megasteps (slot refill), and then
    only through the megastep's own admission arguments."""
    last_token: jax.Array   # (slots,) int32 — input token for next step
    gen_len: jax.Array      # (slots,) int32 — tokens generated so far
    max_new: jax.Array      # (slots,) int32
    eos_id: jax.Array       # (slots,) int32
    phase: jax.Array        # (slots,) int32 — PHASE_IDLE/PREFILL/DECODE
    prefill_pos: jax.Array  # (slots,) int32 — next prompt index to feed
    prompt_len: jax.Array   # (slots,) int32 — total prompt length
    chunk_base: jax.Array   # (slots,) int32 — prompt index of buf[:, 0]
    prompt_buf: jax.Array   # (slots, prefill_chunk) int32 — prompt chunk
    temperature: jax.Array  # (slots,) float32 — per-slot sampling
    top_k: jax.Array        # (slots,) int32
    top_p: jax.Array        # (slots,) float32
    rng: jax.Array          # PRNG key (one split per decode substep)


def _init_slot_state(slots: int, chunk: int, rng: jax.Array) -> SlotState:
    return SlotState(
        last_token=jnp.zeros((slots,), jnp.int32),
        gen_len=jnp.zeros((slots,), jnp.int32),
        max_new=jnp.zeros((slots,), jnp.int32),
        eos_id=jnp.full((slots,), -1, jnp.int32),
        phase=jnp.full((slots,), PHASE_IDLE, jnp.int32),
        prefill_pos=jnp.zeros((slots,), jnp.int32),
        prompt_len=jnp.zeros((slots,), jnp.int32),
        chunk_base=jnp.zeros((slots,), jnp.int32),
        prompt_buf=jnp.zeros((slots, chunk), jnp.int32),
        temperature=jnp.zeros((slots,), jnp.float32),
        top_k=jnp.zeros((slots,), jnp.int32),
        top_p=jnp.ones((slots,), jnp.float32),
        rng=rng)


class ServingEngine:
    def __init__(self, model: Model, params, *, slots: int = 4,
                 max_len: int = 1024,
                 sampling: SamplingConfig = SamplingConfig(),
                 extra_inputs: Optional[Dict[str, Any]] = None,
                 rng: Optional[jax.Array] = None,
                 megastep_k: Optional[int] = None,
                 megastep_unroll: bool = False,
                 admission: str = "chunked",
                 prefill_chunk: Optional[int] = None,
                 donate_carries: bool = True,
                 quant_policy: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 kernels: Optional[str] = None,
                 pipeline_depth: int = 1,
                 page_size: int = 0,
                 cache_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 max_queue: int = 0):
        # Kernel backend is a serving dimension like kv_quant: one
        # switch lights up the whole fused-dequant Pallas path (the
        # quant_matmul decode GEMVs *and* the quantized-KV decode
        # attention kernel) or pins everything to XLA. As with
        # kv_quant, a request that differs from the model's config
        # rebinds the engine to a same-params Model view.
        if kernels is not None:
            if kernels not in ("xla", "pallas"):
                raise ValueError(
                    f"kernels must be 'xla' or 'pallas' (got {kernels!r})")
            if kernels != model.cfg.kernels:
                model = Model(dataclasses.replace(model.cfg,
                                                  kernels=kernels))
        self.kernels = model.cfg.kernels
        # Cache precision is a serving dimension parallel to
        # ``quant_policy`` (the *other* memory-bound decode stream — and
        # the one that grows with context length and batch). The model's
        # cache path keys off ``cfg.kv_quant``, so a requested format
        # that differs from the model's config rebinds the engine to a
        # same-params Model view with the format applied. Recurrent
        # families (ssm/hybrid) serve bf16 state regardless
        # (``Model.kv_quant_effective``).
        if kv_quant is not None:
            if kv_quant not in ("bf16", "q8_0", "q4_0"):
                raise ValueError(
                    f"kv_quant must be bf16|q8_0|q4_0 (got {kv_quant!r})")
            if kv_quant != model.cfg.kv_quant:
                model = Model(dataclasses.replace(model.cfg,
                                                  kv_quant=kv_quant))
        self.model = model
        self.cfg = model.cfg
        self.kv_quant = model.kv_quant_effective()
        # Quantization is a serving dimension (paper §5.3: Q4 halves the
        # memory-roofline cost of the decode GEMVs). ``quant_policy``
        # quantizes the weight pytree on entry; already-quantized leaves
        # pass through untouched, so handing the engine pre-quantized
        # params with a matching policy is a no-op — and a *mismatched*
        # pre-quantized tree is rejected rather than silently served
        # under the wrong label.
        if quant_policy and quant_policy not in ("bf16", "f16", "f32"):
            for leaf in jax.tree_util.tree_leaves(
                    params,
                    is_leaf=lambda x: isinstance(x, QuantizedTensor)):
                if isinstance(leaf, QuantizedTensor) and \
                        leaf.fmt != quant_policy:
                    raise ValueError(
                        f"params already quantized as {leaf.fmt!r}; "
                        f"cannot serve them under quant_policy="
                        f"{quant_policy!r} (re-quantizing int weights "
                        "would compound error — dequantize first)")
            params = quantize_tree(params, quant_policy,
                                   model.cfg.quant_group)
        self.quant_policy = quant_policy or "bf16"
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.sampling = sampling
        self.extra = extra_inputs or {}
        self._init_rng = rng if rng is not None else jax.random.PRNGKey(0)
        if megastep_k is not None and int(megastep_k) < 1:
            raise ValueError(
                f"megastep_k must be >= 1 (got {megastep_k}); "
                "K is the number of decode tokens per fused dispatch")
        self.megastep_k = int(megastep_k) if megastep_k else \
            DEFAULT_MEGASTEP_K
        # unrolling the K-substep scan lets XLA fuse *across* decode
        # iterations (deeper amortization than the launch cost alone)
        # at compile time ∝ K — worth it for small dispatch-bound models
        self.megastep_unroll = megastep_unroll

        if admission not in ("chunked", "stall"):
            raise ValueError(f"admission must be 'chunked' or 'stall' "
                             f"(got {admission!r})")
        # chunked admission feeds raw token ids through decode_step; it
        # cannot synthesize encoder frames / VLM prefix embeddings, so
        # those archs keep the batched-prefill admission path.
        if self.cfg.arch_type in ("audio", "vlm") or self.extra:
            admission = "stall"
        self.admission = admission
        # prompt tokens staged on device per slot; the host refreshes
        # the chunk through the megastep's admission args, so any value
        # >= megastep_k admits without ever starving the scan
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else \
            max(self.megastep_k, 16)
        self.donate_carries = donate_carries
        # dispatched-but-undrained megasteps the loop keeps in flight:
        # 1 = serial dispatch→drain (the PR-1/2 loop), 2 = double-
        # buffered (drain N overlaps megastep N+1 on device). Host-side
        # orchestration only — the compiled megastep is depth-agnostic,
        # so the attribute may be reassigned between steps.
        if int(pipeline_depth) < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1 (got {pipeline_depth}); "
                "1 is the serial loop, 2 keeps one megastep in flight")
        self.pipeline_depth = int(pipeline_depth)
        # PR 6 measured this combination as pathological on jax-CPU:
        # donating a still-pending computation's output buffer makes
        # the next jit call run inline, serializing exactly the
        # dispatch the pipelining exists to overlap. Warn + override
        # rather than raise so callers still get a working (and
        # faster) configuration.
        if self.pipeline_depth > 1 and self.donate_carries:
            warnings.warn(
                "pipeline_depth>1 with donate_carries=True serializes "
                "dispatch (donating a pending megastep's carry makes "
                "the next dispatch run inline on this backend); "
                "overriding donate_carries=False", RuntimeWarning,
                stacklevel=2)
            self.donate_carries = donate_carries = False

        # EDF-ordered admission queue; ``max_queue`` bounds it (0 =
        # unbounded — the pre-overload-PR behaviour) and submit() sheds
        # with a typed reject instead of growing it past the bound.
        if int(max_queue) < 0:
            raise ValueError(f"max_queue must be >= 0 (got {max_queue})")
        self.max_queue = int(max_queue)
        self.queue: Deque[Request] = collections.deque()
        # run audit() after every step() (the launch.serve --audit flag)
        self.audit_every_step = False

        # recurrent state makes padding unsound → exact-length buckets
        self._pad_prefill = self.cfg.arch_type not in ("ssm", "hybrid")
        window = model.window_for(max_len)
        self._has_window = bool(window)
        self._cache_seq = min(max_len, window) if window else max_len

        # -- paged KV cache (block pool + per-slot block tables) ------
        # ``page_size`` > 0 virtualizes full-attention caches: total
        # cache memory is ``cache_blocks`` pool blocks (scaling with
        # live tokens, not slots × max_len), slot retirement/cancel
        # recycles blocks through a free list, and — with
        # ``prefix_cache`` — admission maps a prompt's longest cached
        # prefix into the new slot's table copy-on-write. Recurrent and
        # sliding-window families stay structurally dense (a contract
        # no-op, like kv_quant there).
        if int(page_size) < 0:
            raise ValueError(f"page_size must be >= 0 (got {page_size})")
        self.page_size = int(page_size)
        self._eff_page = model.paging_effective(max_len, self.page_size)
        self.paged = bool(self._eff_page)
        if self.paged and self._cache_seq % self._eff_page:
            raise ValueError(
                f"page_size {self._eff_page} must divide the cache "
                f"length {self._cache_seq} so the gathered paged view "
                "stays shape-identical to the dense cache")
        self.max_pages = (self._cache_seq // self._eff_page
                          if self.paged else 0)
        if self.paged:
            default_blocks = self.slots * self.max_pages + 1
            self.cache_blocks = (int(cache_blocks) if cache_blocks
                                 else default_blocks)
            if self.cache_blocks < 2:
                raise ValueError(
                    f"cache_blocks must be >= 2 (got {cache_blocks}): "
                    "block 0 is the reserved garbage block")
        else:
            self.cache_blocks = 0
        # prefix reuse needs chunked admission: only then are a
        # prompt's pages produced by the same compiled megastep every
        # admission path runs, so shared pages are bit-identical to
        # what a fresh prefill would write (the XLA-CPU one-ulp
        # cross-regime caveat in ROADMAP standing notes).
        self.prefix_cache_enabled = bool(
            prefix_cache and self.paged and self.admission == "chunked")
        if prefix_cache and not self.prefix_cache_enabled:
            warnings.warn(
                "prefix_cache requires a paged cache and chunked "
                "admission; disabled for this engine", RuntimeWarning,
                stacklevel=2)

        # donated carries: cache + SlotState are consumed by the
        # dispatch and updated in place (we immediately rebind both).
        # ``all_greedy`` is static: an all-greedy batch (the common
        # serving benchmark configuration) compiles a pure-argmax
        # sampler, skipping sample_batched's per-substep full-vocab
        # sorts; the stochastic variant compiles lazily on first use.
        donate = (1, 2) if donate_carries else ()
        self._megastep = jax.jit(self._megastep_impl,
                                 donate_argnums=donate,
                                 static_argnums=(4,))
        donate_pf = (3, 5) if donate_carries else ()
        self._prefill = jax.jit(self._prefill_impl,
                                donate_argnums=donate_pf)
        self.reset(rng=self._init_rng)

    def reset(self, rng: Optional[jax.Array] = None) -> None:
        """Drop all requests and device state (fresh cache + slots);
        compiled megastep/prefill executables are kept, so a reset
        engine re-serves without re-tracing."""
        if rng is not None:
            self._init_rng = rng
        st_key = jax.random.split(self._init_rng)[1]
        self.cache = self.model.init_cache(
            self.slots, self.max_len, page_size=self.page_size,
            cache_blocks=self.cache_blocks)
        self.state = _init_slot_state(self.slots, self.prefill_chunk,
                                      st_key)
        self.active: List[Optional[Request]] = [None] * self.slots
        # block allocator (paged only): free list + refcounts. Block 0
        # is the reserved garbage block (frozen-row writes land there)
        # and is never handed out. ``_prefix_reg`` maps a prompt
        # prefix's content key → pool block, LRU-ordered; the registry
        # holds its own reference so a cached page survives its
        # original request.
        self._free: List[int] = (list(range(self.cache_blocks - 1, 0, -1))
                                 if self.paged else [])
        self._ref = (np.zeros((self.cache_blocks,), np.int64)
                     if self.paged else None)
        self._slot_blocks: List[List[int]] = [[] for _ in
                                              range(self.slots)]
        self._slot_shared: List[int] = [0] * self.slots
        self._slot_reg_done: List[bool] = [False] * self.slots
        self._prefix_reg: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()
        # pipelined loop: (device block, slot-occupant snapshot,
        # megastep ordinal) per dispatched-but-undrained megastep,
        # oldest first
        self._inflight: Deque = collections.deque()
        # host mirror of prefill progress (from the megastep's pos row)
        self._prefill_pos: List[int] = [0] * self.slots
        # the prompt each slot was admitted with (the *effective*
        # prompt: original + pre-preemption tokens for resumed
        # requests) — chunk refills must window over this, not the
        # request's live fields, which keep growing during decode
        self._slot_prompt: List[Optional[np.ndarray]] = \
            [None] * self.slots
        # slots currently serving a stochastic (temperature>0) request;
        # empty → the megastep compiles/runs its argmax-only variant
        self._stochastic_slots: set = set()
        # blocks withheld from the allocator (fault injection / admission
        # headroom) — a first-class owner class the audit partitions on
        self._quarantined: List[int] = []
        # uids whose logits the megastep overwrites with NaN (the
        # fault-injection surface for poisoned-request isolation)
        self._poison_uids: set = set()
        self._submit_seq = 0
        self.queue.clear()
        self.stats = EngineStats()

    def cache_nbytes(self) -> int:
        """Device bytes of the live cache pytree (int8 payload + scale
        leaves for quantized caches)."""
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(self.cache))

    # -- block allocator (paged cache) -------------------------------------
    @property
    def blocks_in_use(self) -> int:
        """Pool blocks currently referenced (excl. the garbage block)."""
        if not self.paged:
            return 0
        return self.cache_blocks - 1 - len(self._free)

    def _decref(self, blk: int) -> None:
        self._ref[blk] -= 1
        assert self._ref[blk] >= 0, f"block {blk} refcount underflow"
        if self._ref[blk] == 0:
            self._free.append(blk)
            self.stats.blocks_recycled += 1

    def _release_slot_blocks(self, s: int) -> None:
        """Drop a retired/cancelled slot's references. Blocks shared
        with the prefix registry (or another slot's table) survive —
        only the refcount hitting zero recycles a block."""
        for blk in self._slot_blocks[s]:
            self._decref(blk)
        self._slot_blocks[s] = []
        self._slot_shared[s] = 0
        self._slot_reg_done[s] = False

    def _reserve_blocks(self, n: int) -> bool:
        """Ensure ``n`` free blocks, evicting LRU prefix-registry
        entries if needed (an evicted page still referenced by a live
        slot is only unhooked from the registry, not recycled)."""
        while len(self._free) < n and self._prefix_reg:
            _, blk = self._prefix_reg.popitem(last=False)
            self._decref(blk)
        return len(self._free) >= n

    def _admit_paged(self, s: int, req: Request) -> Optional[int]:
        """Allocate the slot's block table for ``req``; returns the
        admission start position (> 0 on a prefix hit: that many prompt
        tokens are already cached in shared pages) or None when the
        pool cannot supply enough blocks even after registry eviction
        (the caller re-queues the request — FIFO blocking)."""
        P = self._eff_page
        # effective view: a resumed request re-prefills its generated
        # prefix too, and only its remaining budget still allocates
        prompt = self._eff_prompt(req)
        max_new = self._eff_max_new(req)
        need = min(len(prompt) + max_new, self._cache_seq)
        n_pages = -(-need // P)
        # a request that outgrows the cache wraps its ring cursor back
        # over its own leading pages — those pages must be exclusively
        # owned (no sharing in, no registration out)
        wraps = len(prompt) + max_new > self._cache_seq
        shared: List = []
        if self.prefix_cache_enabled and not wraps:
            # longest cached prefix, capped so >= 1 prompt token is
            # left to feed (the scan emits the first sampled token the
            # substep it consumes the last prompt token)
            for i in range((len(prompt) - 1) // P):
                key = prompt[:(i + 1) * P].tobytes()
                blk = self._prefix_reg.get(key)
                if blk is None:
                    break
                shared.append((key, blk))
        if not self._reserve_blocks(n_pages - len(shared)):
            return None
        blocks = []
        for key, blk in shared:
            self._ref[blk] += 1
            self._prefix_reg.move_to_end(key)
            blocks.append(blk)
        for _ in range(n_pages - len(shared)):
            blk = self._free.pop()
            self._ref[blk] = 1
            blocks.append(blk)
        self._slot_blocks[s] = blocks
        self._slot_shared[s] = len(shared)
        self._slot_reg_done[s] = wraps or not self.prefix_cache_enabled
        start = len(shared) * P
        if start:
            self.stats.prefix_hits += 1
            self.stats.prefix_hit_tokens += start
        return start

    def _register_prefix(self, s: int, req: Request) -> None:
        """Publish the slot's fully-prefilled prompt pages into the
        prefix registry (chunked admission only — see __init__). Runs
        once per request, when the drained pos mirror shows the prompt
        fully consumed, i.e. after the pages' contents exist on
        device. Decode writes land at pos >= prompt_len, so published
        pages are never written again by this slot (copy-on-write for
        free)."""
        self._slot_reg_done[s] = True
        prompt = np.asarray(req.prompt, np.int32)
        P = self._eff_page
        blocks = self._slot_blocks[s]
        for i in range(len(prompt) // P):
            key = prompt[:(i + 1) * P].tobytes()
            if key in self._prefix_reg:
                self._prefix_reg.move_to_end(key)
                continue
            self._ref[blocks[i]] += 1       # the registry's reference
            self._prefix_reg[key] = blocks[i]

    def _slot_table_row(self, s: int) -> np.ndarray:
        row = np.zeros((self.max_pages,), np.int32)
        blocks = self._slot_blocks[s]
        row[:len(blocks)] = blocks
        return row

    def quarantine_blocks(self, n: int) -> int:
        """Withhold up to ``n`` free blocks from the allocator (returns
        how many were taken). Quarantined blocks are a first-class
        owner class: admissions can't use them, audit() accounts them,
        ``release_quarantined`` returns them. This is the allocator-
        exhaustion fault-injection surface, and doubles as an admission
        headroom reservation."""
        if not self.paged:
            return 0
        n = min(int(n), len(self._free))
        for _ in range(n):
            self._quarantined.append(self._free.pop())
        return n

    def release_quarantined(self, n: Optional[int] = None) -> int:
        """Return up to ``n`` quarantined blocks (all when None) to the
        free list; returns how many were released."""
        k = len(self._quarantined) if n is None else \
            min(int(n), len(self._quarantined))
        for _ in range(k):
            self._free.append(self._quarantined.pop())
        return k

    def audit(self) -> None:
        """Invariant checker (raises ``EngineAuditError``): the free
        list ∪ quarantine ∪ referenced blocks partitions the pool,
        every refcount equals the number of live references (slot
        tables + prefix registry), block 0 is never handed out, no
        request is simultaneously active and queued, and no empty slot
        holds blocks. Host-side structures only — safe to run between
        steps even with a megastep in flight."""
        for s, r in enumerate(self.active):
            if r is None:
                continue
            if r.done or r.cancelled:
                raise EngineAuditError(
                    f"slot {s}: active request {r.uid} is already done")
            if any(q is r for q in self.queue):
                raise EngineAuditError(
                    f"request {r.uid} is both active (slot {s}) and "
                    "queued — a preemption/requeue double-entry")
        if not self.paged:
            return
        refs = np.zeros((self.cache_blocks,), np.int64)
        for s, blocks in enumerate(self._slot_blocks):
            if blocks and self.active[s] is None:
                raise EngineAuditError(
                    f"slot {s}: empty slot still holds blocks {blocks}")
            for b in blocks:
                if not 1 <= b < self.cache_blocks:
                    raise EngineAuditError(
                        f"slot {s}: table maps block {b} (0 is the "
                        "reserved garbage block)")
                refs[b] += 1
        for b in self._prefix_reg.values():
            refs[b] += 1
        free, quar = set(self._free), set(self._quarantined)
        if len(free) != len(self._free):
            raise EngineAuditError("duplicate block in the free list")
        if len(quar) != len(self._quarantined):
            raise EngineAuditError("duplicate block in quarantine")
        if 0 in free or 0 in quar or refs[0] or self._ref[0]:
            raise EngineAuditError("block 0 escaped the garbage role")
        for b in range(1, self.cache_blocks):
            if self._ref[b] != refs[b]:
                raise EngineAuditError(
                    f"block {b}: refcount {int(self._ref[b])} != "
                    f"{int(refs[b])} live references")
            owners = (b in free) + (b in quar) + (refs[b] > 0)
            if owners != 1:
                raise EngineAuditError(
                    f"block {b}: {owners} owners (free={b in free}, "
                    f"quarantined={b in quar}, refs={int(refs[b])}) — "
                    "the pool partition is broken")

    # -- preemption / resume helpers ---------------------------------------
    def _eff_prompt(self, req: Request) -> np.ndarray:
        """Admission-time prompt: the original prompt plus any tokens
        already generated before a preemption. Re-feeding the generated
        prefix through the same decode path rebuilds the cache
        bit-identically, so a resumed greedy request continues exactly
        where an uninterrupted run would."""
        prompt = np.asarray(req.prompt, np.int32)
        if not req.output:
            return prompt
        return np.concatenate(
            [prompt, np.asarray(req.output, np.int32)])

    def _eff_max_new(self, req: Request) -> int:
        """In-slot generation budget: total minus already-emitted."""
        return req.max_new_tokens - len(req.output)

    def _edf_key(self, req: Request):
        d = req._deadline_abs if req._deadline_abs is not None else _INF
        return (d, req._seq)

    def _enqueue(self, req: Request) -> None:
        """Insert keeping the queue sorted by (deadline, submission
        order) — plain FIFO when no request carries a deadline."""
        req._t_queued = time.perf_counter()
        key = self._edf_key(req)
        if not self.queue or self._edf_key(self.queue[-1]) <= key:
            self.queue.append(req)
            return
        for i, r in enumerate(self.queue):
            if self._edf_key(r) > key:
                self.queue.insert(i, req)
                return
        self.queue.append(req)

    def _measured_substep_s(self) -> Optional[float]:
        """Measured wall seconds per decode substep (None before any
        megastep has run) — the basis for retry-after hints and the
        infeasible-deadline shed."""
        if self.stats.steps == 0 or self.stats.decode_wall_s == 0.0:
            return None
        return self.stats.decode_wall_s / self.stats.steps

    def _service_substeps(self, req: Request) -> int:
        """Substeps a request occupies a slot for: chunked admission
        rides the prompt in-scan (one token per substep), stall
        prefills in one dispatch."""
        gen = max(self._eff_max_new(req), 1)
        if self.admission == "chunked":
            return len(self._eff_prompt(req)) + gen
        return gen

    def _pick_victim(self, incoming: Request) -> Optional[int]:
        """Preemption victim for a pool-starved admission: only slots
        whose EDF key is strictly *later* than the incoming request's
        are eligible (later deadline, or same-class but younger), so a
        preempted-and-requeued request can never be preempted back by
        the one that displaced it — no livelock. Among eligible slots:
        non-shared-prefix first (frees more private blocks, loses no
        registry value), then least progress (least recompute)."""
        key = self._edf_key(incoming)
        cands = [s for s, r in enumerate(self.active)
                 if r is not None and not r.done
                 and self._edf_key(r) > key]
        if not cands:
            return None
        return min(cands, key=lambda s: (
            self._slot_shared[s] > 0,
            len(self.active[s].output) + self._prefill_pos[s]))

    def _preempt_slot(self, s: int,
                      admit: Optional[Dict[str, np.ndarray]] = None
                      ) -> Request:
        """Evict slot ``s``: freeze its cache through the same
        PHASE_IDLE path cancel/EOS use (any in-flight megastep keeps
        emitting its pre-preemption tokens, which the drain appends
        before the request is readmitted), recycle its private blocks,
        and requeue the request to recompute from prompt + generated
        prefix. Clears the slot's staged chunk-refill entry when the
        admission arrays are already built."""
        req = self.active[s]
        self.state = dataclasses.replace(
            self.state, phase=self.state.phase.at[s].set(PHASE_IDLE))
        self.active[s] = None
        self._stochastic_slots.discard(s)
        self._prefill_pos[s] = 0
        self._slot_prompt[s] = None
        if self.paged:
            self._release_slot_blocks(s)
        if admit is not None:
            admit["refill"][s] = False
            admit["tokens"][s, :] = 0
            admit["base"][s] = 0
        req.preemptions += 1
        self.stats.preemptions += 1
        self._enqueue(req)
        return req

    def preempt(self, req: Request) -> bool:
        """Preempt an active request (the mechanism behind pool-starved
        admission; also the fault injector's ``preempt`` event).
        Returns False when the request isn't occupying a slot. The
        request resumes via the normal queue — token-identical under
        greedy sampling."""
        if req.done or req.cancelled:
            return False
        for s, r in enumerate(self.active):
            if r is req:
                self._preempt_slot(s)
                return True
        return False

    def inject_logit_poison(self, req: Request) -> None:
        """Fault-injection hook: overwrite this request's logits with
        NaN inside the megastep (while it occupies a slot) so the
        in-jit finiteness check retires it — the deterministic way to
        exercise poisoned-request isolation."""
        self._poison_uids.add(req.uid)

    # -- per-request sampling ----------------------------------------------
    def _req_sampling(self, req: Request):
        smp = self.sampling
        return (
            smp.temperature if req.temperature is None else req.temperature,
            smp.top_k if req.top_k is None else req.top_k,
            smp.top_p if req.top_p is None else req.top_p)

    # -- batched prefill into free slots (admission="stall") ---------------
    def _prefill_impl(self, params, tokens, seq_lens, cache, slot_idx,
                      state, max_new, eos_id, temp, top_k, top_p,
                      table_rows):
        """Prefill a length bucket (N, S) in one dispatch: splice its
        cache rows into the batch cache at ``slot_idx`` (N,), sample
        the first token in-jit, and refill the SlotState rows — the
        whole refill is one dispatch and one (N,) host transfer.

        Paged engines prefill into a *dense* scratch cache (the model's
        prefill path is structure-driven), then scatter its rows
        page-wise into the pool blocks named by ``table_rows``
        (N, max_pages) — dense engines ignore that argument."""
        n = tokens.shape[0]
        one = self.model.init_cache(n, self.max_len)
        batch = {"tokens": tokens, "seq_lens": seq_lens, **{
            k: (jnp.broadcast_to(v[None], (n,) + v.shape)
                if hasattr(v, "shape") else v)
            for k, v in self.extra.items()}}
        logits, one = self.model.prefill(params, batch, one)
        if self.paged:
            new_cache = self._paged_splice(cache, one, slot_idx,
                                           table_rows)
        else:
            axes = self.model.cache_axes()

            def splice(full, single, ax):
                # the batch axis is named per cache leaf by
                # cache_axes(); never guess it from shapes (a leaf with
                # slots==1 or a size-1 non-batch dim would silently
                # mis-splice)
                b = ax.index("batch")
                out = jnp.moveaxis(full, b, 0).at[slot_idx].set(
                    jnp.moveaxis(single, b, 0).astype(full.dtype))
                return jnp.moveaxis(out, 0, b)

            new_cache = jax.tree_util.tree_map(splice, cache, one, axes)

        rng, key = jax.random.split(state.rng)
        first = sample_batched(logits, key, temp, top_k, top_p)
        alive = (first != eos_id) & (max_new > 1)
        phase = jnp.where(alive, PHASE_DECODE, PHASE_IDLE)
        new_state = dataclasses.replace(
            state,
            last_token=state.last_token.at[slot_idx].set(first),
            gen_len=state.gen_len.at[slot_idx].set(1),
            max_new=state.max_new.at[slot_idx].set(max_new),
            eos_id=state.eos_id.at[slot_idx].set(eos_id),
            phase=state.phase.at[slot_idx].set(phase),
            prefill_pos=state.prefill_pos.at[slot_idx].set(
                seq_lens.astype(jnp.int32)),
            prompt_len=state.prompt_len.at[slot_idx].set(
                seq_lens.astype(jnp.int32)),
            temperature=state.temperature.at[slot_idx].set(temp),
            top_k=state.top_k.at[slot_idx].set(top_k),
            top_p=state.top_p.at[slot_idx].set(top_p),
            rng=rng)
        return first, new_cache, new_state

    def _paged_splice(self, cache, one, slot_idx, table_rows):
        """Scatter a dense prefilled scratch cache into the paged live
        cache: K/V (and scale) rows are cut into page_size chunks and
        written to the pool blocks the admitted slots' tables name;
        ``lens`` and ``block_table`` rows are spliced per slot. Pages
        past a slot's allocation map to table entry 0 — the garbage
        block — so over-long (length-bucketed) scratch rows land
        harmlessly there."""
        P = self._eff_page
        live, scratch = cache["layers"], one["layers"]
        out = dict(live)
        S = scratch["k"].shape[3]
        n_pages = min(-(-S // P), self.max_pages)
        for name in ("k", "v", "k_scale", "v_scale"):
            if name not in live:
                continue
            src = scratch[name].astype(live[name].dtype)
            L, n, Hkv, _, d = src.shape
            pad = n_pages * P - S
            if pad > 0:
                src = jnp.pad(src, ((0, 0), (0, 0), (0, 0), (0, pad),
                                    (0, 0)))
            elif pad < 0:
                src = src[:, :, :, :n_pages * P]
            src = src.reshape(L, n, Hkv, n_pages, P, d)
            src = jnp.moveaxis(src, 3, 2)    # (L, n, n_pages, Hkv, P, d)
            out[name] = live[name].at[:, table_rows[:, :n_pages]].set(src)
        out["lens"] = live["lens"].at[:, slot_idx].set(
            scratch["lens"].astype(live["lens"].dtype))
        out["block_table"] = live["block_table"].at[:, slot_idx].set(
            table_rows[None].astype(jnp.int32))
        new_cache = dict(cache, layers=out)
        for name in ("cross_k", "cross_v", "cross_lens"):
            if name in cache:
                b = 0 if name == "cross_lens" else 1
                merged = jnp.moveaxis(cache[name], b, 0).at[slot_idx].set(
                    jnp.moveaxis(one[name], b, 0).astype(
                        cache[name].dtype))
                new_cache[name] = jnp.moveaxis(merged, 0, b)
        return new_cache

    def _bucket_len(self, prompt_len: int) -> int:
        """Padded bucket length: next power of two (≥8), capped at the
        cache window so padded prefill never hits the ring path. Exact
        length for recurrent archs and over-window prompts."""
        if not self._pad_prefill or prompt_len > self._cache_seq:
            return prompt_len
        return min(max(8, 1 << (prompt_len - 1).bit_length()),
                   self._cache_seq)

    # -- public API --------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request. Admission edge cases are resolved here, not
        in-scan: an empty prompt is rejected (it would feed a junk PAD
        token through ``decode_step`` into cache position 0), and
        ``max_new_tokens=0`` short-circuits to an empty completed
        output (the in-scan path checks ``gen_len >= max_new`` only
        *after* emission, so an admitted zero-budget request would
        still emit one token). Overload rejects are typed (see the
        module docstring's failure-semantics section): ``PromptTooLong``
        for prompts that can never fit, ``QueueFull`` at the
        ``max_queue`` bound, ``InfeasibleDeadline`` when
        ``req.deadline_s`` can't be met by an empty engine."""
        req.t_submit = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.submit", uid=req.uid):
            self._submit(req)

    def _submit(self, req: Request) -> None:
        if len(np.asarray(req.prompt)) == 0:
            raise ValueError(
                f"request {req.uid}: empty prompt — decode needs at "
                "least one prompt token (admitting one would write a "
                "junk PAD embedding into cache position 0)")
        if req.max_new_tokens < 0:
            raise ValueError(
                f"request {req.uid}: max_new_tokens must be >= 0 "
                f"(got {req.max_new_tokens})")
        if req.max_new_tokens == 0:
            req.done = True          # nothing to generate: legal no-op
            return
        prompt_len = len(np.asarray(req.prompt))
        if self.paged:
            need = min(prompt_len + req.max_new_tokens, self._cache_seq)
            pages = -(-need // self._eff_page)
            if pages > self.cache_blocks - 1:
                raise PromptTooLong(
                    f"request {req.uid}: needs {pages} cache pages but "
                    f"the pool holds {self.cache_blocks - 1} — it can "
                    "never be admitted (raise cache_blocks or shrink "
                    "the request)", uid=req.uid)
        elif (not self._has_window
                and self.cfg.arch_type not in ("ssm", "hybrid")
                and prompt_len > self._cache_seq):
            # full-attention dense cache: prefilling past the slot's
            # rows scatters out of range and corrupts the stream —
            # reject at admission instead (windowed/recurrent caches
            # wrap/accumulate legally, paged caches ring over their
            # own pages)
            raise PromptTooLong(
                f"request {req.uid}: prompt of {prompt_len} tokens "
                f"exceeds the cache capacity {self._cache_seq} "
                f"(max_len={self.max_len}) — the prefill write would "
                "corrupt the slot's cache; raise max_len or truncate",
                uid=req.uid)
        if self.max_queue and len(self.queue) >= self.max_queue:
            self.stats.shed += 1
            sub = self._measured_substep_s()
            hint = None
            if sub is not None:
                backlog = sum(self._service_substeps(r)
                              for r in self.queue)
                hint = sub * backlog / max(self.slots, 1)
            raise QueueFull(
                f"request {req.uid}: queue at its bound "
                f"({self.max_queue}) — shed to protect latency; retry "
                f"after {hint if hint is not None else 'the drain'}",
                uid=req.uid, retry_after_s=hint,
                queue_depth=len(self.queue))
        if req.deadline_s is not None:
            sub = self._measured_substep_s()
            est = (sub or 0.0) * self._service_substeps(req)
            if req.deadline_s <= 0 or est > req.deadline_s:
                self.stats.shed += 1
                raise InfeasibleDeadline(
                    f"request {req.uid}: deadline {req.deadline_s:.3f}s "
                    f"< estimated service {est:.3f}s even unqueued — "
                    "shed instead of generating tokens it can't use",
                    uid=req.uid, queue_depth=len(self.queue))
            req._deadline_abs = time.monotonic() + req.deadline_s
        req._seq = self._submit_seq
        self._submit_seq += 1
        self._enqueue(req)

    def cancel(self, req: Request) -> bool:
        """Retire a request immediately. A queued request is removed
        from the queue; an active one has its slot forced to
        ``PHASE_IDLE`` — the same frozen-write retirement the in-scan
        EOS/length path uses, so the remaining substeps of any
        in-flight megastep leave its cache untouched and its late
        tokens are dropped at drain time. The freed slot is refilled
        at the next admission. Returns True if the request was live.

        Cancel composes with preemption: a request cancelled while
        mid-preemption (requeued, blocks already recycled) takes the
        queue path below — its slot and blocks were released at
        preemption time, so nothing double-frees; a request preempted
        after being cancelled is impossible (``preempt`` refuses
        cancelled requests)."""
        if req.done:
            return False
        self._poison_uids.discard(req.uid)
        try:
            self.queue.remove(req)
            req.done = req.cancelled = True
            self.stats.cancelled += 1
            return True
        except ValueError:
            pass
        for s, r in enumerate(self.active):
            if r is req:
                self.state = dataclasses.replace(
                    self.state,
                    phase=self.state.phase.at[s].set(PHASE_IDLE))
                self.active[s] = None
                self._stochastic_slots.discard(s)
                self._slot_prompt[s] = None
                if self.paged:
                    # recycle the slot's blocks; prefix pages shared
                    # with the registry or another slot survive (their
                    # refcount stays > 0)
                    self._release_slot_blocks(s)
                req.done = req.cancelled = True
                self.stats.cancelled += 1
                return True
        return False

    @property
    def in_flight(self) -> int:
        """Megasteps dispatched but not yet drained (< pipeline_depth
        except transiently inside ``step()``)."""
        return len(self._inflight)

    def has_work(self) -> bool:
        """True while anything is queued, occupying a slot, or riding
        an undrained megastep — the front-end's idle test."""
        return bool(self.queue) or bool(self._inflight) or \
            any(r is not None for r in self.active)

    def _placed(self, req: Request) -> None:
        """Stamp ``req``'s placement in a slot: an ``engine.admit``
        span carrying its wait since it last entered the queue."""
        req.t_admit = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "engine.admit", uid=req.uid,
                queued_us=(req.t_admit - req._t_queued) * 1e6):
            pass

    def _first_token(self, req: Request) -> None:
        """Stamp ``req``'s first output token reaching the host: an
        ``engine.first_token`` span carrying admit → first token
        (``prefill_us``) and submit → first token (``ttft_us``)."""
        req.t_first_token = t = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "engine.first_token", uid=req.uid,
                prefill_us=(t - req.t_admit) * 1e6,
                ttft_us=(t - req.t_submit) * 1e6):
            pass

    def _take_free(self) -> List:
        free = [s for s in range(self.slots) if self.active[s] is None]
        # a preempted request still riding an undrained megastep's
        # occupant snapshot must not be readmitted yet: that drain will
        # append its pre-preemption tokens, and a premature resume
        # would re-generate them (duplicated output, early retirement)
        pending = {id(r) for _, occ, _ in self._inflight
                   for r in occ if r is not None}
        taken, held = [], []
        while free and self.queue:
            req = self.queue.popleft()
            # a preempted-then-finished (or late-cancelled) request can
            # still sit in the queue: drop it without burning a slot
            if req.done or req.cancelled:
                continue
            if id(req) in pending:
                held.append(req)     # resume after its block drains
                continue
            taken.append((free.pop(0), req))
        # held requests were popped from the head, so putting them back
        # at the head in order preserves the EDF sort
        self.queue.extendleft(reversed(held))
        return taken

    def _fill_slots_stall(self) -> None:
        """PR-1 admission: length-bucketed prefill dispatches that run
        between megasteps — and stall every decoding slot meanwhile.
        Resumed (preempted) requests prefill their prompt + generated
        prefix and keep only their remaining budget in-slot."""
        taken = self._take_free()
        if self.paged and taken:
            # allocate block tables up front; a request the pool cannot
            # serve preempts an eligible victim (see _pick_victim) or
            # goes back to the queue head (FIFO blocking — later
            # requests must not jump an admission-starved head)
            admitted, putback = [], []
            for s, req in taken:
                if putback:
                    putback.append(req)
                    continue
                res = self._admit_paged(s, req)
                while res is None:
                    v = self._pick_victim(req)
                    if v is None:
                        break
                    self._preempt_slot(v)
                    res = self._admit_paged(s, req)
                if res is None:
                    putback.append(req)
                else:
                    admitted.append((s, req))
            self.queue.extendleft(reversed(putback))
            taken = admitted
        if not taken:
            return
        buckets: Dict[int, List] = {}
        for s, req in taken:
            self._placed(req)
            p = self._eff_prompt(req)
            buckets.setdefault(self._bucket_len(len(p)),
                               []).append((s, req, p))
        for blen, group in buckets.items():
            toks = np.full((len(group), blen), PAD_ID, np.int32)
            for i, (_, _, p) in enumerate(group):
                toks[i, :len(p)] = p
            lens = np.asarray([len(p) for _, _, p in group], np.int32)
            slot_idx = np.asarray([s for s, _, _ in group], np.int32)
            maxnew = np.asarray([self._eff_max_new(r)
                                 for _, r, _ in group], np.int32)
            eos = np.asarray([r.eos_id for _, r, _ in group], np.int32)
            smp = [self._req_sampling(r) for _, r, _ in group]
            temp = np.asarray([v[0] for v in smp], np.float32)
            topk = np.asarray([v[1] for v in smp], np.int32)
            topp = np.asarray([v[2] for v in smp], np.float32)
            rows = (np.stack([self._slot_table_row(s)
                              for s, _, _ in group])
                    if self.paged
                    else np.zeros((len(group), 0), np.int32))
            first, self.cache, self.state = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                self.cache, jnp.asarray(slot_idx), self.state,
                jnp.asarray(maxnew), jnp.asarray(eos),
                jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(topp),
                jnp.asarray(rows))
            first = np.asarray(first)
            self.stats.prefill_batches += 1

            for i, (s, req, p) in enumerate(group):
                tok = int(first[i])
                req.output.append(tok)
                if len(req.output) == 1:
                    self._first_token(req)
                self.stats.prefills += 1
                self.stats.tokens_generated += 1
                self._feed(s, 0, len(p))
                if tok == req.eos_id or len(req.output) >= \
                        req.max_new_tokens:
                    req.done = True       # first token already ends it
                    if self.paged:
                        self._release_slot_blocks(s)
                else:
                    self.active[s] = req
                    self._slot_prompt[s] = p
                    if self._req_sampling(req)[0] > 0.0:
                        self._stochastic_slots.add(s)

    def _empty_admit(self) -> Dict[str, np.ndarray]:
        n, c = self.slots, self.prefill_chunk
        admit = {"new": np.zeros((n,), bool),
                 "refill": np.zeros((n,), bool),
                 "tokens": np.zeros((n, c), np.int32),
                 "base": np.zeros((n,), np.int32),
                 "prompt_len": np.zeros((n,), np.int32),
                 "max_new": np.zeros((n,), np.int32),
                 "eos": np.full((n,), -1, np.int32),
                 "temp": np.zeros((n,), np.float32),
                 "top_k": np.zeros((n,), np.int32),
                 "top_p": np.ones((n,), np.float32),
                 # slots whose logits the fault injector corrupts
                 # in-jit (NaN) this megastep — exercises the same
                 # finiteness-retirement path a real nonfinite model
                 # output would take
                 "poison": np.zeros((n,), bool)}
        if self.paged:
            # fresh slots' admission start (cached-prefix length) and
            # block-table rows ride the same megastep arguments
            admit["start_pos"] = np.zeros((n,), np.int32)
            admit["block_table"] = np.zeros((n, self.max_pages),
                                            np.int32)
        return admit

    def _fill_slots_chunked(self) -> Dict[str, np.ndarray]:
        """Build the megastep's admission arguments: next prompt chunk
        for slots mid-prefill, first chunk + metadata for fresh
        requests. No model dispatch happens here — the arrays ride into
        the already-scheduled megastep, so admission costs zero host
        dispatches beyond the megastep cadence."""
        admit = self._empty_admit()
        chunk = self.prefill_chunk
        # refresh the chunk window for slots still consuming a prompt
        # (windowed over the admitted effective prompt — a resumed
        # request's live fields keep growing during decode)
        for s, req in enumerate(self.active):
            if req is None:
                continue
            pos = self._prefill_pos[s]
            prompt = self._slot_prompt[s]
            if prompt is None or pos >= len(prompt):
                continue
            admit["refill"][s] = True
            admit["base"][s] = pos
            seg = prompt[pos:pos + chunk]
            admit["tokens"][s, :len(seg)] = seg
            if pos > 0:
                self.stats.chunk_refills += 1
        # admit fresh requests to free slots
        putback: List[Request] = []
        for s, req in self._take_free():
            start = 0
            prompt = self._eff_prompt(req)
            if self.paged:
                if putback:
                    putback.append(req)   # FIFO: stay behind the
                    continue              # blocked head
                res = self._admit_paged(s, req)
                while res is None:
                    # pool exhausted even after registry eviction:
                    # preempt an eligible victim (strictly later EDF
                    # key — see _pick_victim) or block FIFO
                    v = self._pick_victim(req)
                    if v is None:
                        break
                    self._preempt_slot(v, admit)
                    res = self._admit_paged(s, req)
                if res is None:           # pool exhausted: re-queue
                    putback.append(req)
                    continue
                start = res
                admit["start_pos"][s] = start
                admit["block_table"][s] = self._slot_table_row(s)
            admit["new"][s] = True
            admit["base"][s] = start
            seg = prompt[start:start + chunk]
            admit["tokens"][s, :len(seg)] = seg
            admit["prompt_len"][s] = len(prompt)
            admit["max_new"][s] = self._eff_max_new(req)
            admit["eos"][s] = req.eos_id
            temp, topk, topp = self._req_sampling(req)
            admit["temp"][s] = temp
            admit["top_k"][s] = topk
            admit["top_p"][s] = topp
            self.active[s] = req
            self._placed(req)
            self._slot_prompt[s] = prompt
            self._prefill_pos[s] = start
            if temp > 0.0:
                self._stochastic_slots.add(s)
            self.stats.prefills += 1
            self.stats.inscan_admissions += 1
        if putback:
            self.queue.extendleft(reversed(putback))
        return admit

    def _fill_slots(self) -> Dict[str, np.ndarray]:
        if self.admission == "chunked":
            admit = self._fill_slots_chunked()
        else:
            self._fill_slots_stall()
            admit = self._empty_admit()
        if self._poison_uids:
            for s, r in enumerate(self.active):
                if r is not None and r.uid in self._poison_uids:
                    admit["poison"][s] = True
        return admit

    # -- fused K-token decode + in-scan admission ---------------------------
    def _merge_admissions(self, cache, st: SlotState, admit):
        """Fold the host's admission arrays into the carry, inside the
        megastep jit. Fresh slots get their cache rows zeroed (every
        family's init state is zeros; attention junk past ``lens`` is
        never read) and their SlotState rows rebuilt; chunk refills
        only swap the prompt window."""
        nm = jnp.asarray(admit["new"])
        anym = nm | jnp.asarray(admit["refill"])
        axes = self.model.cache_axes(page_size=self._eff_page)

        def reset(leaf, ax):
            if "batch" not in ax:
                # paged pool leaves have no per-slot rows to zero;
                # stale block contents past ``lens`` are never read
                # (same contract as dense junk past lens)
                return leaf
            b = ax.index("batch")
            m = nm.reshape(tuple(nm.shape[0] if i == b else 1
                                 for i in range(leaf.ndim)))
            return jnp.where(m, jnp.zeros((), leaf.dtype), leaf)

        cache = jax.tree_util.tree_map(reset, cache, axes)
        if self.paged:
            # fresh slots start at their cached-prefix length with the
            # host-allocated block table mapped in
            start = jnp.asarray(admit["start_pos"])
            tbl = jnp.asarray(admit["block_table"])
            lay = dict(cache["layers"])
            lay["lens"] = jnp.where(nm[None, :], start[None, :],
                                    lay["lens"])
            lay["block_table"] = jnp.where(nm[None, :, None], tbl[None],
                                           lay["block_table"])
            cache = dict(cache, layers=lay)
            start_pos = start
        else:
            start_pos = jnp.zeros_like(st.prefill_pos)
        new_state = SlotState(
            last_token=jnp.where(nm, 0, st.last_token),
            gen_len=jnp.where(nm, 0, st.gen_len),
            max_new=jnp.where(nm, admit["max_new"], st.max_new),
            eos_id=jnp.where(nm, admit["eos"], st.eos_id),
            phase=jnp.where(nm, PHASE_PREFILL, st.phase),
            prefill_pos=jnp.where(nm, start_pos, st.prefill_pos),
            prompt_len=jnp.where(nm, admit["prompt_len"], st.prompt_len),
            chunk_base=jnp.where(anym, admit["base"], st.chunk_base),
            prompt_buf=jnp.where(anym[:, None], admit["tokens"],
                                 st.prompt_buf),
            temperature=jnp.where(nm, admit["temp"], st.temperature),
            top_k=jnp.where(nm, admit["top_k"], st.top_k),
            top_p=jnp.where(nm, admit["top_p"], st.top_p),
            rng=st.rng)
        return cache, new_state

    def _megastep_impl(self, params, cache, state, admit, all_greedy):
        """K decode substeps in one ``lax.scan``: admission merge,
        in-jit per-slot sampling, per-slot EOS/length retirement via
        the frozen-write mask. Prefilling slots feed prompt tokens from
        their chunk buffer instead of ``last_token`` and stay silent
        until the last prompt position. ``all_greedy`` (static) traces
        a pure-argmax sampler when no active slot is stochastic.

        Every substep checks per-slot logits for NaN/inf: a nonfinite
        slot emits nothing, is forced to PHASE_IDLE (so subsequent
        substeps freeze its cache writes — the same retirement path EOS
        takes), and is flagged in the packed block's fourth row for the
        host to error-retire. Other slots in the batch are untouched —
        their logits, sampling, and cache writes never see the bad
        slot's values. ``admit["poison"]`` lets the fault injector
        corrupt a slot's logits in-jit to exercise exactly this path.

        Returns (cache, state, block (4, K, slots) = tokens / emitted /
        prefill progress / nonfinite flag)."""
        cache, state = self._merge_admissions(cache, state, admit)
        chunk = self.prefill_chunk
        poison = jnp.asarray(admit["poison"])

        def body(carry, _):
            cache, st = carry
            is_pre = st.phase == PHASE_PREFILL
            is_dec = st.phase == PHASE_DECODE
            off = jnp.clip(st.prefill_pos - st.chunk_base, 0, chunk - 1)
            ptok = jnp.take_along_axis(st.prompt_buf, off[:, None],
                                       axis=1)[:, 0]
            # a prefill slot whose chunk window ran dry idles (cache
            # frozen) until the host refreshes the buffer — can only
            # happen when prefill_chunk < megastep_k
            starved = is_pre & (st.prefill_pos - st.chunk_base >= chunk)
            feeding = is_pre & ~starved
            in_tok = jnp.where(is_pre, ptok, st.last_token)
            advance = feeding | is_dec
            logits, cache = self.model.decode_step(
                params, in_tok[:, None], cache, advance_mask=advance)
            logits = jnp.where(poison[:, None],
                               jnp.full((), jnp.nan, logits.dtype),
                               logits)
            finite = jnp.all(jnp.isfinite(logits), axis=-1)
            bad = (is_pre | is_dec) & ~finite
            rng, step_key = jax.random.split(st.rng)
            if all_greedy:
                tok = sample(logits, step_key, _GREEDY)
            else:
                tok = sample_batched(logits, step_key, st.temperature,
                                     st.top_k, st.top_p)
            finishing = feeding & (st.prefill_pos + 1 >= st.prompt_len)
            emit = (is_dec | finishing) & ~bad
            tok = jnp.where(emit, tok, jnp.int32(PAD_ID))
            gen_len = st.gen_len + emit.astype(jnp.int32)
            done_now = emit & ((tok == st.eos_id) |
                               (gen_len >= st.max_new))
            phase = jnp.where(
                emit, jnp.where(done_now, PHASE_IDLE, PHASE_DECODE),
                st.phase)
            # a nonfinite slot retires through the frozen-write path:
            # IDLE phase means every later substep's advance mask and
            # emit mask exclude it, exactly like EOS
            phase = jnp.where(bad, PHASE_IDLE, phase)
            new_st = dataclasses.replace(
                st,
                last_token=jnp.where(emit, tok, st.last_token),
                gen_len=gen_len,
                phase=phase,
                prefill_pos=st.prefill_pos + feeding.astype(jnp.int32),
                rng=rng)
            return (cache, new_st), (tok, emit, new_st.prefill_pos, bad)

        (cache, state), (toks, emitted, pos, flagged) = jax.lax.scan(
            body, (cache, state), None, length=self.megastep_k,
            unroll=self.megastep_unroll)
        # pack (tokens, emitted, prefill progress, nonfinite flags)
        # into one (4, K, slots) block → a single device→host transfer
        return cache, state, jnp.stack(
            [toks, emitted.astype(jnp.int32), pos,
             flagged.astype(jnp.int32)])

    def _dispatch_megastep(self) -> bool:
        """Dispatch half of the pipelined loop: stage admissions from
        the host's current slot view and launch one megastep. Dispatch
        is asynchronous under JAX — the returned block rides
        ``_inflight`` (with a snapshot of its slot occupants) until
        ``_drain_oldest`` synchronizes on it. Returns False when no
        slot is live in the host view (nothing to launch)."""
        n0 = self.stats.prefills
        with _timed(self.stats, "stage_wall_s", "engine.stage") as span:
            admit = self._fill_slots()
            span.set_metadata(admitted=self.stats.prefills - n0)
        if not any(r is not None for r in self.active):
            return False
        n = self.stats.megasteps + 1
        with jax.profiler.TraceAnnotation("engine.dispatch", megastep=n):
            self.cache, self.state, block = self._megastep(
                self.params, self.cache, self.state, admit,
                not self._stochastic_slots)
        self._inflight.append((block, tuple(self.active), n))
        self.stats.megasteps = n
        self.stats.steps += self.megastep_k
        return True

    def _drain_oldest(self) -> None:
        """Drain half: block on the oldest in-flight megastep's packed
        token block (the loop's one sync point), then attribute tokens
        and retirements to the requests that occupied the slots *when
        that megastep was dispatched* — under pipelining the host view
        may have moved on (a slot freed by an earlier drain can
        already hold a newer request, whose rows in this older block
        are all idle)."""
        block, occupants, n = self._inflight.popleft()
        with _timed(self.stats, "drain_wait_s", "engine.drain_wait",
                    megastep=n):
            block = np.asarray(block)    # ONE host transfer per K tokens
        with jax.profiler.TraceAnnotation("engine.drain") as span:
            emitted, fed, attended = self._absorb(block, occupants)
            span.set_metadata(emitted=emitted, fed=fed, attended=attended)

    def _feed(self, s: int, start: int, end: int) -> int:
        """Slot ``s`` ran prompt tokens ``start:end`` through the model:
        move its prompt cursor to ``end`` and count them in
        ``stats.prompt_tokens_fed``; returns how many."""
        self._prefill_pos[s] = end
        self.stats.prompt_tokens_fed += end - start
        return end - start

    def _cache_len(self, s: int, req: Request) -> int:
        """Positions ``req`` holds in slot ``s``'s cache, in the host
        view: the prompt fed so far, then the prompt and every emitted
        token but the last (which the next substep feeds)."""
        if req.output:
            return len(req.prompt) + len(req.output) - 1
        return self._prefill_pos[s] if self.active[s] is req else 0

    def _absorb(self, block: np.ndarray, occupants) -> tuple:
        """Apply one drained block to the host view; returns (tokens
        emitted, prompt tokens fed, cache positions attended) by the
        requests still served. A slot that grows from ``n`` to
        ``n + a`` positions attended ``n + 1, ..., n + a`` of them on
        its ``a`` substeps."""
        toks, emitted = block[0], block[1].astype(bool)
        last_pos = block[2][-1]
        bad = block[3].astype(bool).any(axis=0)
        held = {s: self._cache_len(s, r) for s, r in enumerate(occupants)
                if r is not None}
        n_emitted = n_fed = 0
        for s in range(self.slots):
            # advance the prompt-cursor mirror only while the slot
            # still serves the request this block belongs to — a stale
            # pos row from a retired occupant must never leak into a
            # newer request's chunk-refill base. Nonfinite slots are
            # about to be error-retired: don't advance their mirror or
            # publish their pages to the prefix registry.
            if (occupants[s] is not None and not bad[s]
                    and self.active[s] is occupants[s]):
                n_fed += self._feed(s, self._prefill_pos[s],
                                    int(last_pos[s]))
                # prompt fully consumed → its pages now exist on
                # device: publish them to the prefix registry
                if (self.prefix_cache_enabled
                        and not self._slot_reg_done[s]
                        and self._prefill_pos[s]
                        >= len(occupants[s].prompt)):
                    self._register_prefix(s, occupants[s])
        for k in range(toks.shape[0]):
            for s in range(self.slots):
                req = occupants[s]
                if req is None or req.cancelled or not emitted[k, s]:
                    continue
                tok = int(toks[k, s])
                req.output.append(tok)
                if len(req.output) == 1:
                    self._first_token(req)
                n_emitted += 1
                self.stats.tokens_generated += 1
                if tok == req.eos_id or len(req.output) >= \
                        req.max_new_tokens:
                    req.done = True      # device already froze this slot
                    if self.active[s] is req:
                        self.active[s] = None
                        self._stochastic_slots.discard(s)
                        self._slot_prompt[s] = None
                        if self.paged:
                            self._release_slot_blocks(s)
        # error-retire slots the device flagged nonfinite: the scan
        # already froze them (no emit, no cache writes past the flag),
        # the host marks the request failed and recycles its slot.
        # Tokens the request emitted *before* the poison landed were
        # appended above — the error reports what it got.
        for s in range(self.slots):
            if not bad[s]:
                continue
            req = occupants[s]
            if req is None or req.done or req.cancelled:
                continue
            req.error = "nonfinite-logits"
            req.done = True
            self.stats.poisoned += 1
            self._poison_uids.discard(req.uid)
            if self.active[s] is req:
                self.active[s] = None
                self._stochastic_slots.discard(s)
                self._slot_prompt[s] = None
                if self.paged:
                    self._release_slot_blocks(s)
        n_attended = 0
        for s, n in held.items():
            a = max(self._cache_len(s, occupants[s]) - n, 0)
            n_attended += a * n + a * (a + 1) // 2
        return n_emitted, n_fed, n_attended

    def step(self) -> int:
        """Admit what fits, dispatch one megastep (up to ``megastep_k``
        tokens per decoding slot), and drain the oldest in-flight block
        once ``pipeline_depth`` megasteps are outstanding — at depth 1
        that is the megastep just dispatched (serial); at depth 2 the
        previous one, so its drain and the next admission staging
        overlap the dispatched megastep's device execution. Returns
        #slots still occupied in the host view."""
        t0 = time.perf_counter()
        if self._dispatch_megastep():
            while len(self._inflight) >= max(self.pipeline_depth, 1):
                self._drain_oldest()
        else:
            # nothing live in the host view: flush the pipeline so
            # in-flight retirements land and admission can resume
            while self._inflight:
                self._drain_oldest()
        self.stats.decode_wall_s += time.perf_counter() - t0
        if self.audit_every_step:
            self.audit()
        return sum(r is not None for r in self.active)

    def run(self, max_steps: int = 10000) -> None:
        """Drain queue + active slots (``max_steps`` megasteps)."""
        for _ in range(max_steps):
            if not self.has_work():
                return
            self.step()
