"""Per-assigned-architecture smoke tests (deliverable f).

Each test instantiates a REDUCED variant of the same family (2-3
layers, d_model <= 512, <= 4 experts) and runs one forward/train step
plus a decode step on CPU, asserting output shapes and no NaNs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.models import Model
from repro.training import (AdamWConfig, TrainConfig, init_state,
                            make_train_step)


def _batch(cfg, B=2, S=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, S), 0,
                                          cfg.vocab_size)}
    if cfg.arch_type == "audio":
        batch["frames"] = jax.random.normal(
            ks[1], (B, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    if cfg.arch_type == "vlm":
        batch["prefix"] = 0.1 * jax.random.normal(
            ks[1], (B, cfg.num_prefix_embeddings, cfg.d_model),
            jnp.bfloat16)
    return batch


@pytest.fixture(scope="module")
def models():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = reduced(get_config(arch))
            m = Model(cfg)
            cache[arch] = (cfg, m, m.init(jax.random.PRNGKey(0)))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_shapes_no_nans(models, arch):
    cfg, m, params = models(arch)
    B, S = 2, 16
    logits, aux = m.forward(params, _batch(cfg, B, S))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_one_train_step(models, arch):
    cfg, m, params = models(arch)
    step = jax.jit(make_train_step(
        m, TrainConfig(adamw=AdamWConfig(warmup_steps=1, total_steps=10))))
    opt = init_state(params)
    batch = _batch(cfg)
    batch["labels"] = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(params2)[0]
    assert not np.allclose(np.asarray(l0, np.float32),
                           np.asarray(l1, np.float32))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_step_shapes(models, arch):
    cfg, m, params = models(arch)
    B = 2
    cache = m.init_cache(B, 64)
    logits, cache = m.prefill(params, _batch(cfg, B, 8), cache)
    assert logits.shape == (B, cfg.vocab_size)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits2, cache2 = m.decode_step(params, tok, cache)
    assert logits2.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


@pytest.mark.parametrize("arch", ["deepseek-7b", "mamba2-2.7b",
                                  "recurrentgemma-2b"])
def test_decode_matches_forward(models, arch):
    """prefill + decode == full forward (last-token logits)."""
    cfg, m, params = models(arch)
    B, S, G = 2, 12, 2
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S + G), 0,
                              cfg.vocab_size)
    cache = m.init_cache(B, 64)
    logits, cache = m.prefill(params, {"tokens": toks[:, :S]}, cache)
    for t in range(G):
        logits, cache = m.decode_step(params, toks[:, S + t:S + t + 1],
                                      cache)
    full, _ = m.forward(params, {"tokens": toks})
    a = np.asarray(logits, np.float32)
    b = np.asarray(full[:, -1], np.float32)
    assert np.abs(a - b).max() / (np.abs(b).max() + 1e-6) < 0.05


def test_quantized_model_forward():
    cfg = dataclasses.replace(reduced(get_config("deepseek-7b")),
                              quant_policy="q4_0")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    logits, _ = m.forward(params, _batch(cfg))
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_quantized_matches_bf16_closely():
    cfg = reduced(get_config("deepseek-7b"))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0), quantize=False)
    from repro.quant import quantize_tree
    q8 = quantize_tree(params, "q8_0")
    batch = _batch(cfg)
    l_bf16, _ = m.forward(params, batch)
    l_q8, _ = Model(dataclasses.replace(cfg, quant_policy="q8_0")
                    ).forward(q8, batch)
    a = np.asarray(l_bf16, np.float32)
    b = np.asarray(l_q8, np.float32)
    assert np.abs(a - b).max() / (np.abs(a).max() + 1e-6) < 0.15


def test_exact_assigned_configs():
    """The full configs must carry the exact assigned hyperparameters."""
    import repro.configs as C
    spec = {
        "mamba2-2.7b": dict(num_layers=64, d_model=2560, vocab_size=50280,
                            ssm_state=128, d_ff=0),
        "qwen1.5-110b": dict(num_layers=80, d_model=8192, num_heads=64,
                             num_kv_heads=8, d_ff=49152,
                             vocab_size=152064, qkv_bias=True),
        "paligemma-3b": dict(num_layers=18, d_model=2048, num_heads=8,
                             num_kv_heads=1, d_ff=16384,
                             vocab_size=257216),
        "seamless-m4t-medium": dict(num_layers=12, d_model=1024,
                                    num_heads=16, num_kv_heads=16,
                                    d_ff=4096, vocab_size=256206,
                                    is_encoder_decoder=True),
        "kimi-k2-1t-a32b": dict(num_layers=61, d_model=7168,
                                num_heads=64, num_kv_heads=8, d_ff=2048,
                                vocab_size=163840, num_experts=384,
                                experts_per_token=8),
        "deepseek-7b": dict(num_layers=30, d_model=4096, num_heads=32,
                            num_kv_heads=32, d_ff=11008,
                            vocab_size=102400),
        "mistral-nemo-12b": dict(num_layers=40, d_model=5120,
                                 num_heads=32, num_kv_heads=8,
                                 d_ff=14336, vocab_size=131072),
        "phi3.5-moe-42b-a6.6b": dict(num_layers=32, d_model=4096,
                                     num_heads=32, num_kv_heads=8,
                                     d_ff=6400, vocab_size=32064,
                                     num_experts=16, experts_per_token=2),
        "deepseek-67b": dict(num_layers=95, d_model=8192, num_heads=64,
                             num_kv_heads=8, d_ff=22016,
                             vocab_size=102400),
        "recurrentgemma-2b": dict(num_layers=26, d_model=2560,
                                  num_heads=10, num_kv_heads=1,
                                  d_ff=7680, vocab_size=256000),
    }
    for arch, wants in spec.items():
        cfg = C.get_config(arch)
        for k, v in wants.items():
            assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_param_counts_plausible():
    """Sanity: parameter counts land near the headline sizes."""
    expect = {
        "mamba2-2.7b": (2.4e9, 3.0e9),
        "deepseek-7b": (6.5e9, 7.5e9),
        "deepseek-67b": (63e9, 70e9),
        "qwen1.5-110b": (100e9, 120e9),
        "mistral-nemo-12b": (11e9, 13.5e9),
        "kimi-k2-1t-a32b": (0.95e12, 1.15e12),
        "phi3.5-moe-42b-a6.6b": (39e9, 45e9),
        "recurrentgemma-2b": (2.3e9, 3.3e9),
        "paligemma-3b": (2.2e9, 3.2e9),    # language tower only (stub ViT)
        "seamless-m4t-medium": (0.5e9, 1.4e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n / 1e9)
    # MoE active params
    kimi = get_config("kimi-k2-1t-a32b")
    active = kimi.param_count(active_only=True)
    assert 25e9 <= active <= 40e9, active / 1e9


def _eager_init_params(specs, rng, dtype):
    """The eager per-leaf construction (f32 normal, scale, cast) that
    ``init_params`` replaced with one fused jit per leaf."""
    from repro.models.params import is_spec_tree_leaf
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=is_spec_tree_leaf)
    keys = jax.random.split(rng, len(leaves))

    def mk(spec, key):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        if spec.init == "small_a":
            return jnp.full(spec.shape, 4.0, dtype)
        dim = (spec.shape[-1] if spec.init == "fan_out" or
               len(spec.shape) < 2 else spec.shape[-2])
        std = spec.scale * (dim ** -0.5)
        return (jax.random.normal(key, spec.shape, jnp.float32) * std
                ).astype(dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [mk(s, k) for s, k in zip(leaves, keys)])


@pytest.mark.parametrize("arch", ["deepseek-7b", "recurrentgemma-2b"])
def test_init_params_bit_identical_to_eager(arch):
    """Fusing each leaf's normal-scale-cast under jit changes no bit of
    the initialized weights."""
    from repro.models.params import init_params
    m = Model(reduced(get_config(arch)))
    specs = m.param_specs()
    got = init_params(specs, jax.random.PRNGKey(3), jnp.bfloat16)
    want = _eager_init_params(specs, jax.random.PRNGKey(3), jnp.bfloat16)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
