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


def test_planner_picks_megastep_k():
    """Dispatch-overhead napkin math: K grows as the device step
    shrinks relative to the launch cost, the analytic serving model
    predicts the amortization win, and mixed-load admission planning
    picks chunked admission exactly when stalls cost more than riding."""
    from repro.core import (a17_cpu, choose_megastep_k, megastep_time,
                            simulate_admission, simulate_megastep)
    hw = a17_cpu(2)
    assert choose_megastep_k(hw, step_s=1.0) == 1       # step ≫ dispatch
    assert choose_megastep_k(hw, step_s=1e-5) > 1       # dispatch-bound
    assert choose_megastep_k(hw, step_s=0.0) == 1
    # mixed load: frequent arrivals cap K (admission waits on the scan)
    k_idle = choose_megastep_k(hw, step_s=1e-5)
    k_busy = choose_megastep_k(hw, step_s=1e-5, arrival_rate_per_s=1e4)
    assert 1 <= k_busy < k_idle

    ks = (1, 4, 8, 16)
    from repro.configs.paper_models import PAPER_MODELS
    import dataclasses as dc
    llama = PAPER_MODELS["llama3.2-1b"]
    fast = dc.replace(hw, dispatch_overhead_s=5e-3)     # dispatch-bound
    r = simulate_megastep(llama, fast, ks=ks)
    tps = [r[k].tokens_per_s for k in ks]
    assert tps == sorted(tps) and tps[-1] > tps[0]

    # donated carries: the un-donated boundary copy costs throughput
    t_d = megastep_time(1e-4, hw, 8, carry_bytes=1e9, donate_carries=True)
    t_n = megastep_time(1e-4, hw, 8, carry_bytes=1e9,
                        donate_carries=False)
    assert t_d < t_n
    r_nd = simulate_megastep(llama, fast, ks=(8,), donate_carries=False)
    assert r_nd[8].tokens_per_s < r[8].tokens_per_s

    # admission planning: dispatch-dominated admission-heavy traffic
    # (short prompts, unbatched stalls, short generations) → chunked
    # wins; cheap dispatch + very long prompts + perfect bucketing →
    # stall wins (one fused prefill pass beats 4096 rider substeps)
    heavy = dc.replace(hw, dispatch_overhead_s=5e-2)
    adm = simulate_admission(llama, heavy, k=8, batch=8, prompt_len=4,
                             max_new=8, prefill_bucket=1)
    assert adm["chunked"].tokens_per_s > 1.1 * adm["stall"].tokens_per_s
    cheap = dc.replace(hw, dispatch_overhead_s=1e-7)
    adm2 = simulate_admission(llama, cheap, k=8, batch=4,
                              prompt_len=4096, max_new=8,
                              prefill_bucket=4)
    assert adm2["stall"].tokens_per_s > adm2["chunked"].tokens_per_s


def test_plan_decode_sets_admission_and_donation():
    """The hardware-aware plan carries the serving-loop decisions."""
    from repro.core import TPU_V5E, plan
    from repro.configs.base import INPUT_SHAPES
    cfg = get_config("deepseek-7b")
    p = plan(cfg, INPUT_SHAPES["decode_32k"], TPU_V5E,
             avg_prompt_len=32)
    assert p.megastep_k >= 1
    assert p.admission in ("chunked", "stall")
    # donation pairs with depth: a pipelined plan must NOT donate (the
    # previous carry is still in flight when the next megastep wants
    # the buffer), a depth-1 plan always should
    assert p.donate_carries == (p.pipeline_depth < 2)
    assert "admission=" in p.summary()
    # precision is a first-class plan output: memory-bound decode on
    # TPU wants the 4.5-bit stream; the quality floor can veto it
    assert p.quant_policy == "q4_0"
    assert "quant=" in p.summary()
    assert p.config_overrides()["quant_policy"] == "q4_0"
    p_q8 = plan(cfg, INPUT_SHAPES["decode_32k"], TPU_V5E,
                avg_prompt_len=32, quality_floor_bits=8.0)
    assert p_q8.quant_policy == "q8_0"
    p_bf = plan(cfg, INPUT_SHAPES["decode_32k"], TPU_V5E,
                avg_prompt_len=32, allow_quant=False)
    assert p_bf.quant_policy == "bf16"


# ---------------------------------------------------------------------------
# KV-cache quantization (PR-4): structure, engine param, planner
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


def test_plan_and_simulator_carry_kv_quant():
    """dispatch.plan emits kv_quant beside megastep_k/admission/
    quant_policy (quality-floor veto + recurrent no-op), and
    simulate_kv_precision predicts the context-scaling win."""
    from repro.core import TPU_V5E, a17_cpu, plan, simulate_kv_precision
    from repro.configs.base import INPUT_SHAPES
    cfg = get_config("deepseek-7b")
    p = plan(cfg, INPUT_SHAPES["decode_32k"], TPU_V5E, avg_prompt_len=32)
    assert p.kv_quant == "q4_0"           # compute-rich TPU: 4.5 bits win
    assert "kv_quant=" in p.summary()
    # applying the plan to a ModelConfig must carry the cache precision
    # (config_overrides is the documented way to consume a plan)
    assert p.config_overrides()["kv_quant"] == "q4_0"
    p_floor = plan(cfg, INPUT_SHAPES["decode_32k"], TPU_V5E,
                   avg_prompt_len=32, quality_floor_bits=8.0)
    assert p_floor.kv_quant == "q8_0"
    p_off = plan(cfg, INPUT_SHAPES["decode_32k"], TPU_V5E,
                 avg_prompt_len=32, allow_quant=False)
    assert p_off.kv_quant == "bf16"
    p_train = plan(cfg, INPUT_SHAPES["train_4k"], TPU_V5E)
    assert p_train.kv_quant == "bf16"     # no decode loop to feed
    p_ssm = plan(get_config("mamba2-2.7b"), INPUT_SHAPES["decode_32k"],
                 TPU_V5E, avg_prompt_len=32)
    assert p_ssm.kv_quant == "bf16"       # recurrent: contract no-op

    hw = a17_cpu(2)
    sim = simulate_kv_precision(cfg, hw, kv_lens=(64, 32768), ks=(8,))
    gain = lambda fmt, kvl: (sim[fmt][kvl][8].tokens_per_s
                             / sim["bf16"][kvl][8].tokens_per_s)
    # the cache-stream win exists at long context and grows with it
    assert gain("q8_0", 32768) > 1.02
    assert gain("q8_0", 32768) > gain("q8_0", 64)
    assert gain("q4_0", 32768) > 1.0
    # recurrent families: all formats predict identically (no-op)
    simr = simulate_kv_precision(get_config("recurrentgemma-2b"), hw,
                                 kv_lens=(4096,), ks=(8,))
    assert simr["q4_0"][4096][8].tokens_per_s == \
        simr["bf16"][4096][8].tokens_per_s


def test_plan_kernel_backend_flips_quant_ordering():
    """The planner's kernel_backend knob predicts the fused-dequant
    flip this PR's kernels cause: priced against the XLA backend the
    materialized q4_0 unpack (write + read of a bf16 view) drowns the
    byte win and both weight and cache precision fall back to q8_0;
    priced against the fused Pallas kernels (in-register dequant,
    quantized-width HBM reads) q4_0 wins both. config_overrides emits
    a consistent (kernels, use_pallas) pair either way."""
    from repro.core import TPU_V5E, plan, simulate_kv_precision
    from repro.core.precision import get_format
    from repro.configs.base import INPUT_SHAPES
    cfg = get_config("deepseek-7b")
    shape = INPUT_SHAPES["decode_32k"]
    p_pal = plan(cfg, shape, TPU_V5E, avg_prompt_len=32)  # default
    p_xla = plan(cfg, shape, TPU_V5E, avg_prompt_len=32,
                 kernel_backend="xla")
    assert p_pal.kernel_backend == "pallas"
    assert (p_pal.quant_policy, p_pal.kv_quant) == ("q4_0", "q4_0")
    assert (p_xla.quant_policy, p_xla.kv_quant) == ("q8_0", "q8_0")
    assert "kernels=" in p_pal.summary()
    over_p, over_x = p_pal.config_overrides(), p_xla.config_overrides()
    assert over_p["kernels"] == "pallas" and over_p["use_pallas"]
    assert over_x["kernels"] == "xla" and not over_x["use_pallas"]
    with pytest.raises(ValueError):
        plan(cfg, shape, TPU_V5E, kernel_backend="mosaic")

    # the flip's mechanism, pinned at the format level: only q4_0
    # carries a materialized-unpack tax, so only its effective stream
    # ratio degrades under XLA (q8_0's int8 widen fuses into the dot)
    q4, q8 = get_format("q4_0"), get_format("q8_0")
    assert q4.effective_stream_ratio("pallas") == q4.stream_ratio
    assert q4.effective_stream_ratio("xla") > 1.0   # worse than bf16
    assert q8.effective_stream_ratio("xla") == q8.stream_ratio

    # and at the simulator level: per-backend q4-vs-q8 ordering at the
    # plan's context on the same hardware
    sim_p = simulate_kv_precision(cfg, TPU_V5E, kv_lens=(32768,),
                                  ks=(8,))
    sim_x = simulate_kv_precision(cfg, TPU_V5E, kv_lens=(32768,),
                                  ks=(8,), kernel_backend="xla")
    assert sim_p["q4_0"][32768][8].tokens_per_s > \
        sim_p["q8_0"][32768][8].tokens_per_s
    assert sim_x["q8_0"][32768][8].tokens_per_s > \
        sim_x["q4_0"][32768][8].tokens_per_s


# ---------------------------------------------------------------------------
# Paged KV cache (PR-9): planner knob, donation pairing, byte audit
# ---------------------------------------------------------------------------


def test_plan_never_pairs_pipelining_with_donation():
    """Regression: plan() used to hardcode donate_carries=True even
    when it chose pipeline_depth>1 — a donated carry can't be reused
    while the previous megastep still holds it in flight, so the
    engine had to warn and override at construction. The plan must
    never emit the pair: donation is on exactly when the decode loop
    is unpipelined."""
    from repro.core import TPU_V5E, a17_cpu, plan
    from repro.configs.base import INPUT_SHAPES
    for arch in ("deepseek-7b", "mistral-nemo-12b", "mamba2-2.7b"):
        cfg = get_config(arch)
        for shape in INPUT_SHAPES.values():
            for hw in (TPU_V5E, a17_cpu(2)):
                for hit in (0.0, 0.6):
                    p = plan(cfg, shape, hw, avg_prompt_len=512,
                             prefix_hit_rate=hit)
                    assert not (p.pipeline_depth > 1
                                and p.donate_carries), \
                        (arch, shape.name, hit, p.summary())
                    assert p.donate_carries == (p.pipeline_depth < 2)


def test_engine_overrides_donation_when_pipelined():
    """The engine-side belt to the planner's suspenders: constructing
    a pipelined engine with donated carries warns and overrides to
    donate_carries=False instead of serving stale buffers."""
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


def test_plan_page_size_knob():
    """page_size is emitted only when prefix reuse beats the gather
    tax: 0 at the default (no-reuse) hit rate, a sweep size under
    prefix-heavy traffic, and always 0 for recurrent families."""
    from repro.core import TPU_V5E, plan, simulate_paging
    from repro.configs.base import INPUT_SHAPES
    cfg = get_config("deepseek-7b")
    shape = INPUT_SHAPES["decode_32k"]
    p0 = plan(cfg, shape, TPU_V5E, avg_prompt_len=512)
    assert p0.page_size == 0
    p_hit = plan(cfg, shape, TPU_V5E, avg_prompt_len=512,
                 prefix_hit_rate=0.6)
    assert p_hit.page_size > 0
    assert "page_size=" in p_hit.summary()
    p_ssm = plan(get_config("mamba2-2.7b"), shape, TPU_V5E,
                 avg_prompt_len=512, prefix_hit_rate=0.6)
    assert p_ssm.page_size == 0

    # the analytic twin behind the knob: paged pool bytes sit far
    # below the dense high-water prealloc at long context, and prefix
    # hits buy back rider substeps (none without hits)
    sim = simulate_paging(cfg, TPU_V5E, prompt_len=512, max_new=64,
                          kv_len=4096, hit_rate=0.6)
    assert 0 in sim and sim[0]["pool_bytes"] == sim[0]["dense_bytes"]
    for p in (8, 16, 32):
        assert sim[p]["pool_bytes"] < sim[0]["dense_bytes"]
        assert sim[p]["rider_substeps_saved"] > 0
    sim0 = simulate_paging(cfg, TPU_V5E, prompt_len=512, max_new=64,
                           kv_len=4096, hit_rate=0.0)
    assert all(sim0[p]["rider_substeps_saved"] == 0 for p in (8, 16, 32))
    # recurrent families degenerate to dense (nothing to page)
    simr = simulate_paging(get_config("mamba2-2.7b"), TPU_V5E,
                           prompt_len=512, max_new=64, kv_len=4096,
                           hit_rate=0.6)
    for p in (8, 16, 32):
        assert simr[p]["step"].tokens_per_s == simr[0]["step"].tokens_per_s
        assert simr[p]["rider_substeps_saved"] == 0


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
