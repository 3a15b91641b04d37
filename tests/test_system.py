"""End-to-end behaviour: train a tiny model, checkpoint it, serve it.

This is the full paper pipeline in miniature — training substrate →
quantization (the paper's Q4/Q8 study) → batched serving (the paper's
decode benchmark), all through the public API.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import INPUT_SHAPES, get_config, reduced
from repro.core import plan
from repro.models import Model
from repro.quant import quantize_tree
from repro.serving import Request, SamplingConfig, ServingEngine
from repro.training import (AdamWConfig, DataConfig, TrainConfig, batches,
                            checkpoint, init_state, make_train_step)


def test_train_quantize_serve_pipeline(tmp_path):
    cfg = dataclasses.replace(reduced(get_config("deepseek-7b")),
                              param_dtype="f32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # 1. train
    tcfg = TrainConfig(adamw=AdamWConfig(lr=2e-3, warmup_steps=5,
                                         total_steps=200))
    step = jax.jit(make_train_step(model, tcfg))
    opt = init_state(params)
    it = batches(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                            global_batch=8, kind="copy"))
    first = last = None
    for i in range(40):
        params, opt, metrics = step(
            params, opt,
            {k: jnp.asarray(v) for k, v in next(it).items()})
        if first is None:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert last < first

    # 2. checkpoint round trip
    path = str(tmp_path / "model.msgpack")
    checkpoint.save(path, params)
    params = checkpoint.restore(path)

    # 3. quantize per the paper's Q8 setting and serve batched requests
    qparams = quantize_tree(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params),
        "q8_0")
    qcfg = dataclasses.replace(cfg, quant_policy="q8_0")
    engine = ServingEngine(Model(qcfg), qparams, slots=2, max_len=64)
    reqs = [Request(uid=i, prompt=np.arange(4, dtype=np.int32) + 1,
                    max_new_tokens=8) for i in range(4)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done and len(r.output) == 8 for r in reqs)

    # 4. greedy outputs of quantized vs full model mostly agree
    engine_f = ServingEngine(model, params, slots=1, max_len=64)
    rf = Request(uid=9, prompt=np.arange(4, dtype=np.int32) + 1,
                 max_new_tokens=8)
    engine_f.submit(rf)
    engine_f.run()
    agree = np.mean([a == b for a, b in zip(rf.output, reqs[0].output)])
    assert agree >= 0.5, (rf.output, reqs[0].output)


def test_dispatch_plan_configures_model():
    """The hardware-aware planner's overrides produce a runnable model."""
    cfg = get_config("deepseek-7b")
    p = plan(cfg, INPUT_SHAPES["decode_32k"])
    over = p.config_overrides()
    assert over["fuse_qkv"] is True
    # kernels wins over use_pallas in __post_init__, so pin both to
    # keep this smoke test on the fast XLA path
    small = dataclasses.replace(
        reduced(cfg), **{**over, "use_pallas": False, "kernels": "xla"})
    m = Model(small)
    params = m.init(jax.random.PRNGKey(0))
    logits, _ = m.forward(params, {"tokens": jnp.zeros((2, 8), jnp.int32)})
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert p.summary()  # human-readable report exists


def test_compile_cache_defers_to_env(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, the entry points' helper
    leaves the cache to JAX and sets nothing in code."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_compile_cache_fixed_dir_in_checkout(monkeypatch):
    """Without the variable the cache goes to one fixed directory at the
    root of the checkout (no temp name, pid or time in the path)."""
    import pathlib
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)] * 2
