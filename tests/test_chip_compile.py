"""Main-path Pallas kernels compiled by the TPU compiler for a described
v5e chip, at published model widths, with the tiles ``kernels/ops.py``
picks. Nothing runs: a compile here proves only that Mosaic accepts the
kernel (layouts, shifts, VMEM budget), which interpret mode cannot show.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as _da
from repro.kernels import decode_attention_quant as _daq
from repro.kernels import ops
from repro.kernels import quant_matmul as _qmm
from repro.quant import QuantizedTensor, kv_group_size

MODELS = ("llama3.2-1b", "deepseek-7b")
BATCH = 4          # decode slots: the GEMV M and the attention batch
CACHE_LEN = 1024   # cached positions per slot
GROUP = 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a chip that is not attached is written to the cache but
    can never be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _matmul_shapes(arch):
    """(K, N) of the model's decode GEMVs: the up and the down side."""
    cfg = get_config(arch)
    return [(cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)]


@pytest.mark.parametrize("arch", MODELS)
@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
def test_quant_matmul_compiles(arch, fmt, one_chip):
    for K, N in _matmul_shapes(arch):
        bn = ops._pick_lane_tile(N, _qmm.DEFAULT_BN)
        bk = ops._pick_lane_tile(K, _qmm.DEFAULT_BK, multiple=GROUP)
        assert bn and bk, (K, N)     # the fused path, not the fallback
        rows = K // 2 if fmt == "q4_0" else K

        def f(x, data, scales):
            w = QuantizedTensor(data, scales, fmt, GROUP)
            return _qmm.quant_matmul(x, w, bm=BATCH, bn=bn, bk=bk)

        text = _compiled_text(
            f,
            jax.ShapeDtypeStruct((BATCH, K), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((rows, N), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((K // GROUP, N), jnp.bfloat16,
                                 sharding=one_chip))
        assert "tpu_custom_call" in text


def _attn_shapes(arch, one_chip, payload_dim=None, dtype=jnp.bfloat16):
    """Query, one cache leaf and the lengths, at the model's widths."""
    cfg = get_config(arch)
    B, Hq, Hkv, D = BATCH, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, Hkv, CACHE_LEN, payload_dim or D), dtype,
                              sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    return q, kv, lens


@pytest.mark.parametrize("arch", MODELS)
def test_decode_attention_compiles(arch, one_chip):
    q, kv, lens = _attn_shapes(arch, one_chip)
    bk = ops._pick_tile(CACHE_LEN, _da.DEFAULT_BK)
    text = _compiled_text(
        lambda q, k, v, n: _da.decode_attention(q, k, v, n, bk=bk),
        q, kv, kv, lens)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", MODELS)
@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
def test_decode_attention_quant_compiles(arch, fmt, one_chip):
    D = get_config(arch).head_dim
    q, payload, lens = _attn_shapes(
        arch, one_chip, D // 2 if fmt == "q4_0" else D, jnp.int8)
    groups = D // kv_group_size(D, GROUP, fmt)
    scales = jax.ShapeDtypeStruct(payload.shape[:-1] + (groups,),
                                  jnp.bfloat16, sharding=one_chip)
    bk = ops._pick_tile(CACHE_LEN, _daq.DEFAULT_BK)
    text = _compiled_text(
        lambda q, k, ks, v, vs, n: _daq.decode_attention_quant(
            q, k, ks, v, vs, n, fmt=fmt, bk=bk),
        q, payload, scales, payload, scales, lens)
    assert "tpu_custom_call" in text
