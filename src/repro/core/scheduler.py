"""Topological graph scheduler — the paper's §7 contribution.

Three pieces:

1. ``find_concurrent_gemms`` — analyze a :class:`Graph` for independent
   MUL_MAT sets (the paper's Fig 7 coloring: {Q,K,V} and
   {ffn_gate, ffn_up} share all inputs and no outputs).
2. ``fusion_plan`` — convert those sets into *fusions* (the TPU-native
   realization: one wide GEMM per set, see DESIGN.md §2).
3. ``simulate_version`` — predict throughput of the paper's execution
   versions V0–V3 on a given hardware spec, used by
   ``benchmarks/scheduler_versions.py`` to reproduce Figs 8–10.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.configs.base import ModelConfig
from repro.core import cost_model as cm
from repro.core.graph import Graph, Node, Op, build_decoder_graph
from repro.core.precision import get_format


def _xla_unpack_penalty_s(g: Graph, weight_format: str,
                          hw: cm.HardwareSpec,
                          kernel_backend: str) -> float:
    """Per-dispatch seconds the XLA backend pays to materialize bf16
    weight views (write + read of the unpack) before the consuming
    matmuls. ``graph._mm`` bakes the *fused* dequant model into node
    costs (weight bytes at quantized width, dequant flops in-node), so
    the materialization tax must be charged here, outside the graph.
    Zero for the fused ``"pallas"`` backend and for unquantized or
    lane-convertible (q8_0) formats."""
    fmt = get_format(weight_format)
    # effective - ideal = xla_unpack_bytes/2 per weight (validates the
    # backend name as a side effect)
    extra_ratio = (fmt.effective_stream_ratio(kernel_backend)
                   - fmt.stream_ratio)
    if not extra_ratio:
        return 0.0
    weight_elems = sum(n.weight_bytes for n in g.nodes) \
        / fmt.bytes_per_weight
    # bf16 footprint x extra ratio == elems x xla_unpack_bytes_per_weight
    return weight_elems * 2.0 * extra_ratio \
        / (hw.mem_bw * hw.mem_efficiency)


@dataclasses.dataclass(frozen=True)
class ConcurrentSet:
    """A set of MUL_MAT nodes with identical deps → fusable/parallel."""
    layer: int
    block: str
    node_ids: Tuple[int, ...]
    names: Tuple[str, ...]


def find_concurrent_gemms(g: Graph) -> List[ConcurrentSet]:
    """Group matmuls that share *all* dependencies within a layer.

    This is the paper's dynamic graph analysis (§7.1 step 1): two
    matmuls with the same dep set are independent by construction and
    can be dispatched concurrently (mobile) or fused (TPU).
    """
    groups: Dict[Tuple, List[int]] = {}
    for i, n in enumerate(g.nodes):
        if n.op is not Op.MUL_MAT or not n.weight_bytes:
            continue
        key = (n.layer, n.block, n.deps)
        groups.setdefault(key, []).append(i)
    out = []
    for (layer, block, _deps), ids in groups.items():
        if len(ids) > 1:
            out.append(ConcurrentSet(layer, block, tuple(ids),
                                     tuple(g.nodes[i].name for i in ids)))
    return sorted(out, key=lambda s: s.node_ids)


@dataclasses.dataclass(frozen=True)
class FusionPlan:
    """Which projection fusions to apply (flows into ModelConfig flags)."""
    fuse_qkv: bool
    fuse_gate_up: bool
    n_fused_sets: int
    nodes_saved: int


def fusion_plan(g: Graph) -> FusionPlan:
    sets = find_concurrent_gemms(g)
    qkv = any(s.block == "attn" and len(s.node_ids) >= 2 for s in sets)
    gu = any(s.block == "ffn" and len(s.node_ids) >= 2 for s in sets)
    saved = sum(len(s.node_ids) - 1 for s in sets)
    return FusionPlan(qkv, gu, len(sets), saved)


# ---------------------------------------------------------------------------
# Execution-version simulator (paper §7.2, Figs 8-10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class VersionResult:
    version: str
    step_s: float
    tokens_per_s: float
    n_nodes: int
    detail: str


def simulate_version(cfg: ModelConfig, version: str, *,
                     threads: int = 4, seq: int = 1, kv_len: int = 64,
                     weight_format: str = "f16",
                     batch: int = 1,
                     megastep_k: Optional[int] = None) -> VersionResult:
    """Predict decode throughput for paper versions V0-V3 on the A17.

    - v0: serial schedule, unfused GEMMs (paper baseline, 11.5 tk/s)
    - v1: topological wave schedule — independent GEMMs dispatched
          concurrently (13 tk/s)
    - v2: v1 + tensor-level parallelism inside each GEMM: the wave's
          memory traffic now streams at full multi-core bandwidth (15)
    - v3: v2 but FFN block offloaded to the GPU — every block boundary
          pays a Metal sync (6 tk/s)

    ``megastep_k`` (when set) additionally charges the per-step host
    dispatch cost amortized over a K-token megastep — the same
    dispatch-overhead term that decides the paper's §5 CPU-vs-GPU
    result. ``None`` keeps the paper-calibrated ladder untouched.
    """
    cpu = cm.a17_cpu(threads)
    fused = version in ("v2", "v3")
    g = build_decoder_graph(cfg, seq=seq, kv_len=kv_len, batch=batch,
                            weight_format=weight_format, fused=fused)

    # Calibration notes (EXPERIMENTS.md §Paper-repro): the §7 experiments
    # ran on an instrumented build whose serial baseline (11.5 tk/s)
    # sits below the untouched llama.cpp of Fig 4 (17 tk/s @2t). The
    # version deltas — not the absolute baseline — are the paper's
    # claim, and they fall out of (a) strided-vs-sequential streaming
    # efficiency and (b) barrier count per schedule.
    if version == "v0":
        # serial schedule; intra-op threading partitions each GEMM into
        # strided slices -> poor DRAM row locality (eff 0.66/0.95)
        hw = dataclasses.replace(cpu, mem_efficiency=0.66)
        t = cm.graph_time_serial(g, hw)
        detail = "serial schedule, unfused, strided intra-op threading"
    elif version == "v1":
        # graph-level parallelism: concurrent independent GEMMs, each
        # single-threaded -> sequential streams but imperfect overlap
        t = cm.graph_time_wave(g, cpu, overlap_efficiency=0.78)
        detail = "wave schedule over independent GEMMs"
    elif version == "v2":
        # + tensor parallelism inside fused GEMMs: sequential streaming
        # at aggregate bandwidth, one barrier per wave
        t = cm.graph_time_wave(g, cpu, overlap_efficiency=0.92)
        detail = "wave schedule + intra-GEMM tensor parallelism (fused)"
    elif version == "v3":
        hw = dataclasses.replace(cpu, mem_efficiency=0.92 * 0.95)
        t = cm.graph_time_heterogeneous(g, hw, cm.A17_GPU,
                                        boundary_tags=("ffn",))
        detail = "CPU attention + GPU FFN, per-block Metal sync"
    else:
        raise ValueError(version)
    if megastep_k:
        hw_disp = cm.A17_GPU if version == "v3" else cpu
        t = t + hw_disp.dispatch_overhead_s / megastep_k
        detail += f" + dispatch/{megastep_k}"
    return VersionResult(version, t, cm.tokens_per_second(t, seq * batch),
                         len(g.nodes), detail)


def simulate_megastep(cfg: ModelConfig,
                      hw: Optional[cm.HardwareSpec] = None, *,
                      threads: int = 4, kv_len: int = 64,
                      weight_format: str = "f16", batch: int = 1,
                      ks: Sequence[int] = (1, 4, 8, 16),
                      donate_carries: bool = True,
                      prefill_share: float = 0.0,
                      kernel_backend: str = "pallas",
                      ) -> Dict[int, VersionResult]:
    """Predict serving-loop tok/s as a function of megastep K.

    Per-token device time comes from the v2 (fused wave) schedule; each
    megastep then pays ``hw.dispatch_overhead_s`` once per K tokens —
    the napkin math ``core.dispatch.plan`` uses to choose K.

    ``donate_carries=False`` charges the un-donated carry boundary
    (one extra cache-sized write per dispatch — what the engine's
    ``donate_argnums`` removes). ``prefill_share`` models mixed load
    under chunked admission: that fraction of slot-substeps carries
    prompt tokens instead of emitting decode tokens, so reported
    tok/s scales by ``1 - prefill_share`` (the riders themselves add
    no time — same scan, same shapes).

    ``kernel_backend`` selects the dequant execution model for
    quantized ``weight_format``s: the default ``"pallas"`` is the
    fused in-register dequant the graph nodes already encode;
    ``"xla"`` adds the materialized-unpack stream on top
    (:func:`_xla_unpack_penalty_s`) — the PR-4 regime where q4_0
    decoded *slower* than q8_0 despite streaming half the bytes.
    """
    hw = hw or cm.a17_cpu(threads)
    g = build_decoder_graph(cfg, seq=1, kv_len=kv_len, batch=batch,
                            weight_format=weight_format, fused=True)
    per_tok = cm.graph_time_wave(g, hw, overlap_efficiency=0.92) \
        + _xla_unpack_penalty_s(g, weight_format, hw, kernel_backend)
    carry = cm.decode_carry_bytes(cfg, batch, kv_len)
    out = {}
    for k in ks:
        t = cm.megastep_time(per_tok, hw, k, carry_bytes=carry,
                             donate_carries=donate_carries)
        dec_tokens = k * batch * (1.0 - prefill_share)
        out[k] = VersionResult(
            f"megastep_k{k}", t / k, cm.tokens_per_second(t, 1)
            * dec_tokens,
            len(g.nodes),
            f"1 dispatch / {k} tok; per-token device {per_tok*1e6:.0f}us "
            f"+ dispatch {hw.dispatch_overhead_s/k*1e6:.0f}us"
            + ("" if donate_carries else
               f" + carry copy {carry/ (hw.mem_bw*hw.mem_efficiency)/k*1e6:.0f}us"))
    return out


def simulate_precision(cfg: ModelConfig,
                       hw: Optional[cm.HardwareSpec] = None, *,
                       threads: int = 4, kv_len: int = 64,
                       batch: int = 1,
                       formats: Sequence[str] = ("f16", "q8_0", "q4_0"),
                       ks: Sequence[int] = (1, 8),
                       donate_carries: bool = True,
                       kernel_backend: str = "pallas",
                       ) -> Dict[str, Dict[int, VersionResult]]:
    """Serving throughput across weight precisions × megastep K
    (paper §5.3, Fig 4: F16 vs Q8_0 vs Q4_0).

    Each format rebuilds the decode graph with its
    ``bits_per_weight`` / ``dequant_flops_per_weight`` (via
    ``core.precision``), so the prediction carries both the
    memory-roofline win (weight stream shrinks to 8.5/16 or 4.5/16)
    and the dequant tax that erodes it on compute-poor hardware. On a
    memory-bound decode the ordering must come out q4_0 > q8_0 > f16 —
    when a measured backend inverts it (e.g. XLA dequantizing in a
    separate pass instead of in-kernel), that gap is the actionable
    delta, not noise. Pass ``kernel_backend="xla"`` to *predict* that
    inversion instead of just observing it: the materialized-unpack
    tax re-ranks q4_0 below q8_0 (and below f16 on bandwidth-rich
    parts) exactly as the measured sweep does.
    """
    hw = hw or cm.a17_cpu(threads)
    return {fmt: simulate_megastep(
        cfg, hw, kv_len=kv_len, weight_format=fmt, batch=batch, ks=ks,
        donate_carries=donate_carries, kernel_backend=kernel_backend)
        for fmt in formats}


def simulate_kv_precision(cfg: ModelConfig,
                          hw: Optional[cm.HardwareSpec] = None, *,
                          threads: int = 4, batch: int = 1,
                          formats: Sequence[str] = ("bf16", "q8_0",
                                                    "q4_0"),
                          ks: Sequence[int] = (1, 8),
                          kv_lens: Sequence[int] = (64, 1024, 8192),
                          weight_format: str = "f16",
                          donate_carries: bool = True,
                          kernel_backend: str = "pallas",
                          ) -> Dict[str, Dict[int, Dict[int,
                                                        VersionResult]]]:
    """Serving throughput across KV-cache precisions × megastep K ×
    context length.

    The cache stream is the one that *grows* with context and batch
    (weights don't), so unlike the weight sweep the win here is a
    function of ``kv_len``: at short context the cache bytes are
    negligible next to the weight stream + dispatch floor, at long
    context they dominate and both quantized formats must beat bf16.
    Whether q4_0 or q8_0 leads is the per-element dequant-tax call
    (Fig 4e erosion): on the compute-poor A17 the q4 unpack cost hands
    the win to q8_0 — the same inversion PR 3 measured for weights on
    XLA-CPU — while compute-rich TPUs keep q4_0 ahead. Quantizing the
    cache also
    shrinks the megastep *carry*, so the un-donated boundary term
    scales by the same ``stream_ratio``. Recurrent families
    (ssm/hybrid) serve bf16 state regardless — ``kv_quant`` is a
    contract no-op there, and this simulator reflects that by not
    rescaling their cache stream.

    ``kernel_backend`` selects the dequant execution model: the
    default ``"pallas"`` reads the quantized cache in-register (the
    fused ``decode_attention_quant`` kernel); ``"xla"`` charges the
    materialized bf16 unpack (``dequantize_rows`` every megastep) on
    the cache *read* stream via ``megastep_time``. The carry term
    keeps the plain ``stream_ratio`` either way — storage crossing
    the dispatch boundary is quantized regardless of who dequantizes.

    Returns ``{fmt: {kv_len: {k: VersionResult}}}``.
    """
    hw = hw or cm.a17_cpu(threads)
    noop = cfg.arch_type in ("ssm", "hybrid")
    # the bf16-calibrated step depends only on kv_len, not the format
    per_ctx = {}
    for kvl in kv_lens:
        g = build_decoder_graph(cfg, seq=1, kv_len=kvl, batch=batch,
                                weight_format=weight_format, fused=True)
        per_ctx[kvl] = (cm.graph_time_wave(g, hw,
                                           overlap_efficiency=0.92)
                        + _xla_unpack_penalty_s(g, weight_format, hw,
                                                kernel_backend),
                        cm.decode_carry_bytes(cfg, batch, kvl),
                        len(g.nodes))
    out: Dict[str, Dict[int, Dict[int, VersionResult]]] = {}
    for fmt in formats:
        eff = "bf16" if noop else fmt
        ratio = (1.0 if eff in ("bf16", "f16", "f32")
                 else get_format(eff).stream_ratio)
        per_len: Dict[int, Dict[int, VersionResult]] = {}
        for kvl in kv_lens:
            per_tok, cache, n_nodes = per_ctx[kvl]
            per_k: Dict[int, VersionResult] = {}
            for k in ks:
                t = cm.megastep_time(
                    per_tok, hw, k, carry_bytes=cache * ratio,
                    donate_carries=donate_carries,
                    cache_bytes=cache, kv_format=eff,
                    kernel_backend=kernel_backend)
                per_k[k] = VersionResult(
                    f"kv_{fmt}_ctx{kvl}_k{k}", t / k,
                    cm.tokens_per_second(t, 1) * k * batch,
                    n_nodes,
                    f"cache {cache * ratio / 1e3:.1f}kB/token "
                    f"({eff}), 1 dispatch / {k} tok")
            per_len[kvl] = per_k
        out[fmt] = per_len
    return out


def simulate_admission(cfg: ModelConfig,
                       hw: Optional[cm.HardwareSpec] = None, *,
                       threads: int = 4, k: int = 8, batch: int = 4,
                       prompt_len: int = 32, max_new: int = 32,
                       kv_len: int = 64, weight_format: str = "f16",
                       prefill_bucket: float = 1.0,
                       donate_carries: bool = True,
                       ) -> Dict[str, VersionResult]:
    """Stall-prefill vs chunked-prefill admission, analytically.

    Steady state, one batch turnover (every slot serves one request of
    ``prompt_len`` prompt + ``max_new`` generated tokens):

    - ``stall``: admission runs as separate prefill dispatches between
      megasteps; *every* slot idles for each one. Wall per turnover =
      ``max_new`` substeps + (batch / prefill_bucket) stalls of
      (dispatch overhead + full-prompt prefill compute).
      ``prefill_bucket`` = requests sharing one length-bucketed
      dispatch (batch → perfect bucketing, 1 → worst case).
    - ``chunked``: prompts ride inside the scan, one token per substep
      — zero extra dispatches, but the riding slot spends
      ``prompt_len`` substeps not decoding. Wall per turnover =
      ``prompt_len + max_new`` substeps.

    Returns decode-phase tok/s per mode (the engine benchmark's
    ``mixed_workload`` metric). Chunked wins when the dispatch/stall
    term outweighs the riding cost — exactly the paper's §5
    fixed-cost-vs-FLOPs tradeoff applied to admission.
    """
    hw = hw or cm.a17_cpu(threads)
    g = build_decoder_graph(cfg, seq=1, kv_len=kv_len, batch=batch,
                            weight_format=weight_format, fused=True)
    per_tok = cm.graph_time_wave(g, hw, overlap_efficiency=0.92)
    carry = cm.decode_carry_bytes(cfg, batch, kv_len)
    substep = cm.megastep_time(per_tok, hw, k, carry_bytes=carry,
                               donate_carries=donate_carries) / k
    gp = build_decoder_graph(cfg, seq=max(prompt_len, 1), kv_len=0,
                             batch=1, weight_format=weight_format,
                             fused=True)
    prefill_t = cm.graph_time_wave(gp, hw, overlap_efficiency=0.92) \
        + hw.dispatch_overhead_s
    dec_tokens = batch * max_new

    stall_wall = max_new * substep + (batch / max(prefill_bucket, 1e-9)) \
        * prefill_t
    chunked_wall = (prompt_len + max_new) * substep
    return {
        "stall": VersionResult(
            "admission_stall", stall_wall,
            cm.tokens_per_second(stall_wall, 1) * dec_tokens, len(g.nodes),
            f"{batch/max(prefill_bucket,1e-9):.1f} prefill stalls x "
            f"{prefill_t*1e6:.0f}us per turnover"),
        "chunked": VersionResult(
            "admission_chunked", chunked_wall,
            cm.tokens_per_second(chunked_wall, 1) * dec_tokens,
            len(g.nodes),
            f"{prompt_len} rider substeps x {substep*1e6:.0f}us, "
            "0 extra dispatches"),
    }


def simulate_paging(cfg: ModelConfig,
                    hw: Optional[cm.HardwareSpec] = None, *,
                    threads: int = 4, slots: int = 4, k: int = 8,
                    prompt_len: int = 32, max_new: int = 32,
                    kv_len: int = 64,
                    page_sizes: Sequence[int] = (8, 16, 32),
                    hit_rate: float = 0.0,
                    shared_fraction: float = 0.75,
                    live_tokens: Optional[float] = None,
                    weight_format: str = "f16",
                    kv_quant: str = "bf16",
                    donate_carries: bool = True,
                    kernel_backend: str = "pallas",
                    ) -> Dict[int, Dict]:
    """Dense vs paged KV cache, analytically — the model behind
    ``dispatch.plan``'s page-size knob.

    Three effects move per page size ``P``:

    - **footprint**: the dense engine preallocates
      ``slots x kv_len`` rows; the paged pool holds
      ``live_tokens + slots x P/2`` rows (tail-page fragmentation)
      plus table/garbage-block overhead
      (:func:`cost_model.paged_cache_bytes`) — *this* is the term
      that scales with live tokens instead of provisioned capacity.
    - **gather tax**: every substep materializes a dense view of the
      live cache through the block table (~2 extra passes over the
      live cache stream), charged via ``megastep_time``'s
      ``page_gather_bytes`` — grows with context, shrinks per-page-
      size only via table locality (not modelled; P-independent).
    - **prefix reuse**: under chunked admission a prefix hit maps
      ``hit_rate x shared_fraction x prompt_len`` already-cached
      tokens copy-on-write into the new slot's table, so those rider
      substeps vanish from the turnover wall (the Xiao et al. mobile
      traffic argument: bursty requests share system-prompt
      prefixes). Sharable tokens round *down* to whole pages, so
      small P captures more of the prefix.

    Recurrent/windowed families serve dense state regardless
    (``Model.paging_effective`` contract no-op) — every paged entry
    degenerates to the dense result there.

    Returns ``{page_size: {"step": VersionResult, "pool_bytes": ...,
    "dense_bytes": ..., "bytes_per_live_token": ...,
    "rider_substeps_saved": ...}}`` with page size 0 = the dense
    baseline.
    """
    hw = hw or cm.a17_cpu(threads)
    # mirror Model.paging_effective: recurrent state and windowed
    # rings (explicit sliding_window or the long-context fallback)
    # stay dense
    win = (0 if cfg.arch_type in ("ssm", "hybrid")
           else cfg.sliding_window
           or (cfg.window_long_ctx if kv_len > cfg.max_full_attn
               else 0))
    noop = cfg.arch_type in ("ssm", "hybrid") or bool(win)
    g = build_decoder_graph(cfg, seq=1, kv_len=kv_len, batch=slots,
                            weight_format=weight_format, fused=True)
    per_tok = cm.graph_time_wave(g, hw, overlap_efficiency=0.92) \
        + _xla_unpack_penalty_s(g, weight_format, hw, kernel_backend)
    eff_kv = "bf16" if noop else kv_quant
    ratio = (1.0 if eff_kv in ("bf16", "f16", "f32")
             else get_format(eff_kv).stream_ratio)
    dense_bytes = cm.decode_carry_bytes(cfg, slots, kv_len) * ratio
    bytes_per_token = dense_bytes / max(slots * kv_len, 1)
    if live_tokens is None:
        # steady state: each slot holds its prompt plus half its
        # decode budget on average
        live_tokens = slots * min(prompt_len + max_new / 2.0, kv_len)
    dec_tokens = slots * max_new

    out: Dict[int, Dict] = {}
    for p in (0,) + tuple(page_sizes):
        paged = bool(p) and not noop
        gather = 2.0 * live_tokens * bytes_per_token / max(slots * k, 1) \
            if paged else 0.0
        substep = cm.megastep_time(
            per_tok, hw, k, carry_bytes=dense_bytes,
            donate_carries=donate_carries, kv_format=eff_kv,
            cache_bytes=dense_bytes, kernel_backend=kernel_backend,
            page_gather_bytes=gather) / k
        # chunked turnover: prefix hits drop whole shared pages of
        # rider substeps (floor to pages; >= 1 token always fed)
        shared_tok = 0.0
        if paged and hit_rate > 0.0:
            pages = int(min(shared_fraction * prompt_len,
                            prompt_len - 1) // p)
            shared_tok = hit_rate * pages * p
        wall = (prompt_len - shared_tok + max_new) * substep
        pool = (cm.paged_cache_bytes(
                    live_tokens, p, bytes_per_token=bytes_per_token,
                    active_slots=slots, max_pages=-(-kv_len // p))
                if paged else dense_bytes)
        out[p] = {
            "step": VersionResult(
                f"paging_p{p}" if paged else "paging_dense", wall,
                cm.tokens_per_second(wall, 1) * dec_tokens,
                len(g.nodes),
                (f"pool {pool/1e3:.1f}kB vs dense "
                 f"{dense_bytes/1e3:.1f}kB; "
                 f"{shared_tok:.1f} rider substeps saved/turnover"
                 if paged else
                 f"dense prealloc {dense_bytes/1e3:.1f}kB")),
            "pool_bytes": pool,
            "dense_bytes": dense_bytes,
            "bytes_per_live_token": pool / max(live_tokens, 1.0),
            "rider_substeps_saved": shared_tok,
        }
    return out


def simulate_async_overlap(cfg: ModelConfig,
                           hw: Optional[cm.HardwareSpec] = None, *,
                           threads: int = 4, kv_len: int = 64,
                           weight_format: str = "f16", batch: int = 1,
                           k: int = 8,
                           host_drain_per_token_s: float = 8e-6,
                           depths: Sequence[int] = (1, 2),
                           donate_carries: bool = True,
                           kernel_backend: str = "pallas",
                           ) -> Dict[int, VersionResult]:
    """Serial vs double-buffered serving loop, analytically.

    The host pays a per-megastep gap — draining the packed token block
    (device→host transfer + per-token Python bookkeeping) and staging
    the next admission arrays — modelled as
    ``host_drain_per_token_s * k * batch``. At ``pipeline_depth=1``
    that gap sits between device megasteps; at depth >= 2 dispatch is
    async under JAX, so draining megastep N overlaps the device
    running N+1 and the gap is hidden up to the device-step time
    (:func:`cost_model.megastep_time`'s overlap term). The predicted
    win saturates at ``host / (device + host)`` of the serial wall —
    on a device-bound loop the drain hides completely; on a
    host-bound loop the device starves instead and depth stops
    helping.
    """
    hw = hw or cm.a17_cpu(threads)
    g = build_decoder_graph(cfg, seq=1, kv_len=kv_len, batch=batch,
                            weight_format=weight_format, fused=True)
    per_tok = cm.graph_time_wave(g, hw, overlap_efficiency=0.92) \
        + _xla_unpack_penalty_s(g, weight_format, hw, kernel_backend)
    carry = cm.decode_carry_bytes(cfg, batch, kv_len)
    host = host_drain_per_token_s * k * batch
    boundary = 0.0 if donate_carries else \
        carry / (hw.mem_bw * hw.mem_efficiency)
    device = boundary + k * per_tok
    out = {}
    for d in depths:
        t = cm.megastep_time(per_tok, hw, k, carry_bytes=carry,
                             donate_carries=donate_carries,
                             host_drain_s=host, pipeline_depth=d)
        out[d] = VersionResult(
            f"pipeline_depth{d}", t / k,
            cm.tokens_per_second(t, 1) * k * batch, len(g.nodes),
            f"device {device*1e6:.0f}us + host drain {host*1e6:.0f}us "
            + ("serial" if d < 2 else
               f"overlapped (hidden {min(host, device)*1e6:.0f}us)")
            + f" + dispatch {hw.dispatch_overhead_s*1e6:.0f}us")
    return out


def simulate_overload(cfg: ModelConfig,
                      hw: Optional[cm.HardwareSpec] = None, *,
                      threads: int = 4, slots: int = 4, k: int = 8,
                      prompt_len: int = 32, max_new: int = 32,
                      page_size: int = 8, cache_blocks: int = 0,
                      arrival_multiples: Sequence[float] = (0.5, 1.0,
                                                           2.0, 3.0),
                      deadline_factor: float = 3.0,
                      horizon_s: Optional[float] = None,
                      weight_format: str = "f16",
                      donate_carries: bool = True,
                      kernel_backend: str = "pallas",
                      ) -> Dict[str, Dict]:
    """Overload behavior of a bounded vs unbounded admission queue,
    analytically — the model behind ``dispatch.plan``'s queue-bound
    knob.

    Capacity first: a request occupies a slot for
    ``prompt_len + max_new`` chunked substeps (prompt rides in-scan),
    and the block pool caps concurrency at
    ``(cache_blocks - 1) // pages_per_request`` slots — whichever is
    smaller sets the service rate ``mu`` (requests/s). Then, per
    arrival rate ``lambda = m * mu``:

    - **bounded queue + EDF + preemption** sheds the excess at
      admission: shed fraction ``max(0, (lambda - mu) / lambda)``,
      queue wait stays ~bounded (``queue_bound / mu``), so admitted
      requests hit a deadline of ``deadline_factor x`` their service
      time as long as the bound is modest — goodput
      ``min(lambda, mu) * max_new * hit`` tok/s holds flat past
      saturation. Preemption rate ~= the pool-starved fraction of
      admissions (arrivals finding all block-budgeted slots busy while
      extra slots idle).
    - **unbounded queue** sheds nothing but its backlog grows
      ``(lambda - mu) * t``; by the end of a ``horizon_s`` window
      (default: 10x the deadline) the queue wait crosses any fixed
      deadline, so only requests arriving in the first
      ``t* = (D - service) * mu / (lambda - mu)`` seconds (D = the
      deadline) finish in time — goodput *decays* with the horizon
      instead of holding. That's the measured cliff the bench shows
      and the reason ``plan`` emits a queue bound at all.

    Returns ``{"capacity": {...}, "sweep": {multiple: {"bounded":
    {...}, "unbounded": {...}}}}`` with shed/preempt/goodput/hit-rate
    entries per point.
    """
    hw = hw or cm.a17_cpu(threads)
    g = build_decoder_graph(cfg, seq=1, kv_len=prompt_len + max_new,
                            batch=slots, weight_format=weight_format,
                            fused=True)
    per_tok = cm.graph_time_wave(g, hw, overlap_efficiency=0.92) \
        + _xla_unpack_penalty_s(g, weight_format, hw, kernel_backend)
    carry = cm.decode_carry_bytes(cfg, slots, prompt_len + max_new)
    substep = cm.megastep_time(per_tok, hw, k, carry_bytes=carry,
                               donate_carries=donate_carries,
                               kernel_backend=kernel_backend) / k
    service_s = (prompt_len + max_new) * substep
    pages_per_req = -(-(prompt_len + max_new) // max(page_size, 1))
    pool_slots = ((cache_blocks - 1) // pages_per_req
                  if cache_blocks else slots)
    max_live = max(1, min(slots, pool_slots))
    mu = max_live / service_s                      # requests/s
    queue_bound = 2 * slots
    deadline_s = deadline_factor * service_s
    if horizon_s is None:
        horizon_s = 10.0 * deadline_s
    # pool-starved admissions preempt: the fraction of slot capacity
    # the block pool can't back (idle slots an arrival would claim if
    # a victim's blocks were recycled)
    preempt_frac = (max(0.0, (min(slots, queue_bound) - max_live)
                        / float(slots)) if cache_blocks else 0.0)

    sweep: Dict[float, Dict] = {}
    for m in arrival_multiples:
        lam = m * mu
        over = max(0.0, lam - mu)
        # bounded: shed keeps the queue at its bound; an admitted
        # request waits its mean queue position (~half the bound)
        # draining at mu
        shed = over / lam if lam > 0 else 0.0
        wait_b = (0.5 * queue_bound / mu) if over > 0 else \
            (0.5 * min(lam, mu) / mu) * service_s
        hit_b = 1.0 if wait_b + service_s <= deadline_s else max(
            0.0, 1.0 - (wait_b + service_s - deadline_s) / deadline_s)
        good_b = min(lam, mu) * max_new * hit_b
        # unbounded: nothing shed, backlog grows over * t; a request
        # arriving at t waits over * t / mu — past t* it misses D
        if over > 0:
            slack = max(deadline_s - service_s, 0.0)
            t_star = slack * mu / over
            hit_u = min(1.0, max(0.0, t_star / horizon_s))
        else:
            hit_u = hit_b
        good_u = min(lam, mu) * max_new * hit_u
        sweep[m] = {
            "arrival_rps": lam,
            "bounded": {"shed_rate": shed,
                        "preempt_rate": (1.0 - shed) * preempt_frac,
                        "deadline_hit_rate": hit_b,
                        "goodput_tok_s": good_b},
            "unbounded": {"shed_rate": 0.0,
                          "preempt_rate": 0.0,
                          "deadline_hit_rate": hit_u,
                          "goodput_tok_s": good_u},
        }
    return {
        "capacity": {
            "service_s_per_request": service_s,
            "drain_s_per_request": 1.0 / mu,
            "max_live_requests": max_live,
            "pages_per_request": pages_per_req,
            "capacity_rps": mu,
            "queue_bound": queue_bound,
            "deadline_s": deadline_s,
        },
        "sweep": sweep,
    }


def backend_throughput(cfg: ModelConfig, backend: str, *,
                       threads: int = 2, weight_format: str = "f16",
                       kv_len: int = 64, seq: int = 1,
                       batch: int = 1) -> float:
    """Tokens/s for the paper's Fig 4 sweep (GPU vs 1-6 CPU threads)."""
    g = build_decoder_graph(cfg, seq=seq, kv_len=kv_len, batch=batch,
                            weight_format=weight_format, fused=False)
    if backend == "gpu":
        t = cm.graph_time_serial(g, cm.A17_GPU)
    elif backend == "cpu":
        t = cm.graph_time_wave(g, cm.a17_cpu(threads))
    else:
        raise ValueError(backend)
    return cm.tokens_per_second(t, seq * batch)
