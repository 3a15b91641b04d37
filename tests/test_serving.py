"""Serving engine + sampler behaviour, across every cache family.

``engine_setup`` is parametrized over the four architecture families
the engine serves — full attention (deepseek), long-context dense
(mistral-nemo), SSM (mamba2) and RG-LRU hybrid with sliding-window
local attention (recurrentgemma) — so every engine test exercises
every cache layout, not just the default arch. It is additionally
parametrized over the quant policy (paper §5.3): every family also
runs with q4_0 weights, plus one q8_0 combination, so scan-over-layers
slicing of QuantizedTensor leaves, prefill cache splicing and the
frozen-write retirement mask are all exercised quantized. Each test's
oracle (``reference_decode`` / manual loops) uses the *same* quantized
params — engine-vs-reference equivalence is exact even though the
quantized token streams differ from bf16's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import Model
from repro.quant import quantize_tree
from repro.serving import Request, SamplingConfig, ServingEngine, sample

ARCHS = ("deepseek-7b", "mistral-nemo-12b", "mamba2-2.7b",
         "recurrentgemma-2b")
SETUPS = ([(a, "bf16") for a in ARCHS] + [(a, "q4_0") for a in ARCHS]
          + [("deepseek-7b", "q8_0")])


@pytest.fixture(scope="module", params=SETUPS,
                ids=[f"{a}-{q}" for a, q in SETUPS])
def engine_setup(request):
    arch, quant = request.param
    cfg = reduced(get_config(arch))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    if quant != "bf16":
        params = quantize_tree(params, quant, cfg.quant_group)
    return cfg, m, params


def test_greedy_sampling_deterministic():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [2.0, 0.0, -1.0]])
    out = sample(logits, jax.random.PRNGKey(0), SamplingConfig())
    assert out.tolist() == [1, 0]


def test_top_k_restricts_support():
    logits = jnp.asarray([[0.0, 10.0, 9.0, -50.0]])
    cfg = SamplingConfig(temperature=1.0, top_k=2)
    for seed in range(20):
        t = int(sample(logits, jax.random.PRNGKey(seed), cfg)[0])
        assert t in (1, 2)


def test_engine_completes_all_requests(engine_setup):
    cfg, m, params = engine_setup
    eng = ServingEngine(m, params, slots=2, max_len=64)
    reqs = [Request(uid=i, prompt=np.arange(4, dtype=np.int32) + i + 1,
                    max_new_tokens=6) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    assert all(len(r.output) == 6 for r in reqs)
    assert eng.stats.prefills == 5


def test_engine_greedy_matches_manual_decode(engine_setup):
    """Stall-admission engine output == hand-rolled prefill+decode
    loop (greedy) — the fused-prefill path oracle."""
    cfg, m, params = engine_setup
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    eng = ServingEngine(m, params, slots=1, max_len=64,
                        admission="stall")
    req = Request(uid=0, prompt=prompt, max_new_tokens=4)
    eng.submit(req)
    eng.run()

    cache = m.init_cache(1, 64)
    logits, cache = m.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                              cache)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        logits, cache = m.decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), cache)
        toks.append(int(jnp.argmax(logits[0])))
    assert req.output == toks


def test_chunked_engine_matches_reference(engine_setup):
    """Chunked-admission engine output == the single-request reference
    decode loop, on every cache family (the in-scan admission oracle;
    randomized sweeps live in test_serving_properties.py)."""
    cfg, m, params = engine_setup
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    eng = ServingEngine(m, params, slots=2, max_len=64,
                        admission="chunked")
    req = Request(uid=0, prompt=prompt, max_new_tokens=6)
    eng.submit(req)
    eng.run()
    assert req.output == m.reference_decode(params, prompt, 6)
    assert eng.stats.prefill_batches == 0


def test_engine_eos_stops_early(engine_setup):
    cfg, m, params = engine_setup
    eng = ServingEngine(m, params, slots=1, max_len=64)
    # discover the greedy first token, then use it as "EOS"
    probe = Request(uid=0, prompt=np.asarray([1, 2, 3], np.int32),
                    max_new_tokens=1)
    eng.submit(probe)
    eng.run()
    eos = probe.output[0]
    eng.reset()
    req = Request(uid=1, prompt=np.asarray([1, 2, 3], np.int32),
                  max_new_tokens=50, eos_id=eos)
    eng.submit(req)
    eng.run()
    assert req.done and len(req.output) == 1


def test_megastep_equivalence_greedy(engine_setup):
    """Megastep K=8 must be token-identical to K=1 greedy decode —
    including mid-block retirement (max_new=11 is not a multiple of 8)
    and slot refill (3 requests share 2 slots)."""
    cfg, m, params = engine_setup
    outs = {}
    for k in (1, 8):
        eng = ServingEngine(m, params, slots=2, max_len=64, megastep_k=k,
                            megastep_unroll=(k == 8))
        reqs = [Request(uid=i,
                        prompt=np.arange(4, dtype=np.int32) + i + 1,
                        max_new_tokens=11) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        outs[k] = [r.output for r in reqs]
    assert outs[1] == outs[8]
    # K=8 used ~8x fewer dispatches for the same tokens
    assert eng.stats.megasteps < eng.stats.steps


def test_megastep_eos_mid_block(engine_setup):
    """EOS inside a K=8 block stops the slot exactly there (the frozen
    write mask keeps the cache uncorrupted for the remaining substeps)."""
    cfg, m, params = engine_setup
    prompt = np.asarray([1, 2, 3], np.int32)
    probe = Request(uid=0, prompt=prompt, max_new_tokens=6)
    eng = ServingEngine(m, params, slots=1, max_len=64, megastep_k=1)
    eng.submit(probe)
    eng.run()
    eos = probe.output[1]                 # stops mid-first-block
    i = probe.output.index(eos)

    eng2 = ServingEngine(m, params, slots=1, max_len=64, megastep_k=8)
    req = Request(uid=1, prompt=prompt, max_new_tokens=50, eos_id=eos)
    eng2.submit(req)
    eng2.run()
    assert req.done
    assert req.output == probe.output[:i + 1]


def test_megastep_max_new_mid_block(engine_setup):
    """max_new_tokens hit inside a K=8 block retires the slot there,
    and the freed slot is refilled for the next queued request."""
    cfg, m, params = engine_setup
    ref = {}
    for k in (1, 8):
        eng = ServingEngine(m, params, slots=1, max_len=64, megastep_k=k)
        reqs = [Request(uid=i,
                        prompt=np.asarray([2, 7, 1, 8], np.int32),
                        max_new_tokens=5) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done and len(r.output) == 5 for r in reqs)
        ref[k] = [r.output for r in reqs]
    assert ref[1] == ref[8]


def test_per_slot_sampling_mixed_batch(engine_setup):
    """Two slots with different temperatures decode in ONE batch: the
    greedy slot's stream matches the single-request reference exactly
    (greedy rows never consume PRNG), the hot slot still completes."""
    cfg, m, params = engine_setup
    prompt = np.asarray([5, 3, 2, 4], np.int32)
    eng = ServingEngine(m, params, slots=2, max_len=64)
    greedy = Request(uid=0, prompt=prompt, max_new_tokens=8)
    hot = Request(uid=1, prompt=prompt, max_new_tokens=8,
                  temperature=1.3, top_k=20)
    eng.submit(greedy)
    eng.submit(hot)
    eng.run()
    assert greedy.done and hot.done
    assert len(greedy.output) == 8 and len(hot.output) == 8
    assert greedy.output == m.reference_decode(params, prompt, 8)
    assert all(0 <= t < cfg.vocab_size for t in hot.output)


def test_batched_prefill_one_dispatch(engine_setup):
    """Stall admission: prompts landing in the same length bucket
    prefill several slots per jitted dispatch (prefill_batches <
    prefills). Recurrent archs bucket by exact length (padding is
    unsound through their state scan), so they pay one dispatch per
    distinct length."""
    cfg, m, params = engine_setup
    eng = ServingEngine(m, params, slots=4, max_len=64,
                        admission="stall")
    for i in range(4):   # lengths 5..8 → all in the pow2-8 bucket
        eng.submit(Request(uid=i,
                           prompt=np.arange(5 + i, dtype=np.int32) + 1,
                           max_new_tokens=4))
    eng.run()
    assert eng.stats.prefills == 4
    expected = 4 if cfg.arch_type in ("ssm", "hybrid") else 1
    assert eng.stats.prefill_batches == expected


def test_chunked_admission_zero_extra_dispatches(engine_setup):
    """Dispatch-count regression: a long prompt arriving mid-decode is
    admitted and chunk-refilled with ZERO host dispatches beyond the
    megastep cadence (dispatches == megasteps; no prefill batches)."""
    cfg, m, params = engine_setup
    eng = ServingEngine(m, params, slots=2, max_len=96, megastep_k=8,
                        prefill_chunk=8)
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32) + 1,
                       max_new_tokens=24))
    eng.step()                     # slot 0 is now mid-decode
    long_p = (np.arange(40) % (cfg.vocab_size - 1) + 1).astype(np.int32)
    req = Request(uid=1, prompt=long_p, max_new_tokens=4)
    eng.submit(req)
    eng.run()
    assert req.done
    assert req.output == m.reference_decode(params, long_p, 4,
                                            max_len=96)
    assert eng.stats.prefill_batches == 0          # no stall dispatches
    assert eng.stats.inscan_admissions == 2
    assert eng.stats.chunk_refills >= 1            # 40 > prefill_chunk=8


def test_engine_rejects_mismatched_prequantized_params():
    """quant_policy must describe what is actually served: handing the
    engine a tree already quantized in a different format raises
    instead of silently mislabeling (re-quantizing int weights would
    compound error)."""
    cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=1)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    q8 = quantize_tree(params, "q8_0", cfg.quant_group)
    with pytest.raises(ValueError, match="already quantized"):
        ServingEngine(m, q8, slots=1, max_len=64, quant_policy="q4_0")
    # matching policy is the documented no-op path — and it must
    # actually serve (catches quantize_tree descending into
    # QuantizedTensor nodes and nesting them)
    eng = ServingEngine(m, q8, slots=1, max_len=64, quant_policy="q8_0")
    assert eng.quant_policy == "q8_0"
    req = Request(uid=0, prompt=np.asarray([3, 1, 4], np.int32),
                  max_new_tokens=3)
    eng.submit(req)
    eng.run()
    assert req.done
    assert req.output == m.reference_decode(q8, req.prompt, 3)


# ---------------------------------------------------------------------------
# KV-cache quantization: structure, engine param
# ---------------------------------------------------------------------------

KV_FORMATS = ("bf16", "q8_0", "q4_0")


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("kv", KV_FORMATS)
def test_cache_axes_match_cache_structure(arch, kv):
    """Regression (the PR-3 stale-aux bug class, for caches): every
    data leaf ``init_cache`` creates — including the new
    ``k_scale``/``v_scale`` leaves — must have a matching ``cache_axes``
    entry of the same rank naming a batch axis, across all four cache
    families × every kv format. A missing/short axis entry breaks the
    engine's prefill splicing and admission reset silently."""
    import dataclasses
    cfg = dataclasses.replace(reduced(get_config(arch)), kv_quant=kv)
    m = Model(cfg)
    cache = m.init_cache(2, 64)
    axes = m.cache_axes()
    c_leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    a_leaves = jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0]
    c_paths = [jax.tree_util.keystr(p) for p, _ in c_leaves]
    a_paths = [jax.tree_util.keystr(p) for p, _ in a_leaves]
    assert c_paths == a_paths, (arch, kv)
    for (path, leaf), (_, ax) in zip(c_leaves, a_leaves):
        assert len(ax) == leaf.ndim, (arch, kv, path, ax, leaf.shape)
        assert ax.count("batch") == 1, (arch, kv, path, ax)
    if kv != "bf16" and cfg.arch_type not in ("ssm", "hybrid"):
        assert any("k_scale" in p for p in c_paths), (arch, kv)


def test_engine_kv_quant_param_rebinds_model():
    """ServingEngine(kv_quant=...) on a bf16-config model serves a
    quantized cache (int8 leaves + scale siblings) and stays
    token-identical to the rebound model's reference loop."""
    cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=1)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_quant"):
        ServingEngine(m, params, slots=1, max_len=64, kv_quant="int3")
    eng = ServingEngine(m, params, slots=2, max_len=64, megastep_k=4,
                        kv_quant="q8_0")
    assert eng.cfg.kv_quant == "q8_0" and eng.kv_quant == "q8_0"
    assert any(l.dtype == jnp.int8
               for l in jax.tree_util.tree_leaves(eng.cache))
    # bits/16: int8 payload + groupwise scales vs the bf16 cache
    bf16_eng = ServingEngine(m, params, slots=2, max_len=64)
    ratio = eng.cache_nbytes() / bf16_eng.cache_nbytes()
    assert abs(ratio - 8.5 / 16) < 0.02, ratio
    req = Request(uid=0, prompt=np.asarray([3, 1, 4, 1, 5], np.int32),
                  max_new_tokens=5)
    eng.submit(req)
    eng.run()
    assert req.output == eng.model.reference_decode(params, req.prompt, 5)


def test_engine_kv_quant_noop_for_recurrent():
    """kv_quant on an SSM engine changes nothing: same bf16 cache
    structure, same tokens."""
    cfg = reduced(get_config("mamba2-2.7b"))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    outs = {}
    for kv in ("bf16", "q4_0"):
        eng = ServingEngine(m, params, slots=1, max_len=64, kv_quant=kv)
        assert eng.kv_quant == "bf16"
        assert all(l.dtype != jnp.int8
                   for l in jax.tree_util.tree_leaves(eng.cache))
        req = Request(uid=0, prompt=np.asarray([2, 7, 1], np.int32),
                      max_new_tokens=4)
        eng.submit(req)
        eng.run()
        outs[kv] = req.output
    assert outs["bf16"] == outs["q4_0"]


# ---------------------------------------------------------------------------
# Paged KV cache: donation pairing, byte audit
# ---------------------------------------------------------------------------


def test_engine_overrides_donation_when_pipelined():
    """Constructing a pipelined engine with donated carries warns and
    overrides to donate_carries=False instead of serving stale
    buffers."""
    import warnings
    cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=1)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    with pytest.warns(RuntimeWarning, match="donate"):
        eng = ServingEngine(m, params, slots=2, max_len=64,
                            pipeline_depth=2, donate_carries=True)
    assert eng.pipeline_depth == 2 and not eng.donate_carries
    # the consistent pair stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng2 = ServingEngine(m, params, slots=2, max_len=64,
                             pipeline_depth=2, donate_carries=False)
    assert not eng2.donate_carries
    # and the overridden engine still serves correctly
    req = Request(uid=0, prompt=np.asarray([3, 1, 4, 1, 5], np.int32),
                  max_new_tokens=5)
    eng.submit(req)
    eng.run()
    assert req.output == m.reference_decode(params, req.prompt, 5)


@pytest.mark.parametrize("page", (0, 8))
@pytest.mark.parametrize("kv", KV_FORMATS)
def test_cache_nbytes_matches_live_pytree(kv, page):
    """Satellite audit: cache_nbytes() — the number every BENCH
    section reports — equals the actual bytes of every live cache
    leaf (pools, block tables, scale planes, lens) for dense and
    paged caches across cache precisions."""
    cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
                  vocab_size=256, num_heads=2, num_kv_heads=1)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServingEngine(m, params, slots=2, max_len=64, kv_quant=kv,
                        page_size=page)
    leaves = jax.tree_util.tree_leaves(eng.cache)
    assert eng.cache_nbytes() == sum(int(np.asarray(l).nbytes)
                                     for l in leaves)
    if page:
        # the paged cache really is the pool+table layout: an int32
        # block table leaf exists and a right-sized pool undercuts the
        # dense slots*max_len prealloc
        assert any(l.dtype == jnp.int32 and l.ndim == 2 for l in leaves)
        small = ServingEngine(m, params, slots=2, max_len=64,
                              kv_quant=kv, page_size=page,
                              cache_blocks=2 * (16 // page) + 1)
        dense = ServingEngine(m, params, slots=2, max_len=64,
                              kv_quant=kv)
        assert small.cache_nbytes() < dense.cache_nbytes()


@pytest.mark.parametrize("slots", [2, 4])
def test_reference_decode_at_engine_width_matches_engine(slots):
    """Greedy tokens from an engine equal the oracle compiled at its slot
    count, for every request of a mixed-length stream. (The 1-row oracle
    may round a near-tied logit differently: bf16 matmuls are not
    batch-width invariant.)"""
    cfg = reduced(get_config("deepseek-7b"))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(1, cfg.vocab_size, size=int(
                rng.integers(4, 13))).astype(np.int32), max_new_tokens=8)
            for i in range(8)]
    eng = ServingEngine(m, params, slots=slots, max_len=64)
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        assert r.output == m.reference_decode(params, r.prompt, 8,
                                              batch=slots), r.uid


# cache layouts the attention families decode through: (kv format,
# sliding window, page size)
FREEZE_CASES = {"bf16": ("bf16", 0, 0), "q8_0": ("q8_0", 0, 0),
                "ring": ("bf16", 16, 0), "paged": ("bf16", 0, 8)}


@pytest.mark.parametrize("case", sorted(FREEZE_CASES))
def test_decode_step_frozen_rows_stay_bit_identical(case):
    """``decode_step`` with a mixed ``advance_mask``: rows where it is
    False keep every cache leaf and ``lens`` bit-identical, and
    advancing rows (cache and logits) equal what a step that advances
    every row gives them — the old write-then-select path's result, as
    rows never mix. Caches hold real rows first; the ring has wrapped.
    A paged pool is compared through each slot's block table (frozen
    rows' writes land in the garbage block, which no table maps)."""
    from repro.models import attention as attn
    kv, window, page = FREEZE_CASES[case]
    cfg = reduced(get_config("deepseek-7b"), kv_quant=kv,
                  sliding_window=window)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, max_len = 4, 32
    cache = m.init_cache(B, max_len, page_size=page)
    lay = cache["layers"]
    if page:
        L, _, n_pages = lay["block_table"].shape
        tbl = 1 + jnp.arange(B * n_pages, dtype=jnp.int32)
        lay["block_table"] = jnp.broadcast_to(tbl.reshape(B, n_pages),
                                              (L, B, n_pages))
    step = jax.jit(m.decode_step)
    rng = np.random.default_rng(0)

    def tokens():
        return jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 1)),
                           jnp.int32)

    for _ in range(20):
        _, cache = step(params, tokens(), cache, jnp.ones(B, bool))
    mask = np.array([False, True, True, False])
    tok = tokens()
    got_logits, got = step(params, tok, cache, jnp.asarray(mask))
    all_logits, every = step(params, tok, cache, jnp.ones(B, bool))
    np.testing.assert_array_equal(np.asarray(got_logits)[mask],
                                  np.asarray(all_logits)[mask])

    def rows(c):
        """Each leaf with the slot on axis 1: (L, B, ...)."""
        c = c["layers"]
        if not page:
            return c
        out = {n: c[n] for n in ("lens", "block_table")}
        for n in ("k", "v"):
            out[n] = jnp.stack([attn.paged_gather(c[n][l],
                                                  c["block_table"][l])
                                for l in range(c[n].shape[0])])
        return out

    before, got, every = rows(cache), rows(got), rows(every)
    assert set(got) >= {"k", "v", "lens"}
    for name in got:
        g = np.asarray(got[name])
        np.testing.assert_array_equal(g[:, ~mask],
                                      np.asarray(before[name])[:, ~mask],
                                      err_msg=name)
        np.testing.assert_array_equal(g[:, mask],
                                      np.asarray(every[name])[:, mask],
                                      err_msg=name)
    assert (np.asarray(got["lens"])[:, mask]
            == np.asarray(before["lens"])[:, mask] + 1).all()
