"""Main-path Pallas kernels compiled by the TPU compiler for a described
v5e chip, at published model widths, with the tiles ``kernels/ops.py``
picks. Nothing runs: a compile here proves only that Mosaic accepts the
kernel (layouts, shifts, VMEM budget), which interpret mode cannot show.
The serving megastep is compiled too, at a small size, to guard what its
optimized HLO must not hold.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, reduced
from repro.kernels import decode_attention as _da
from repro.kernels import decode_attention_quant as _daq
from repro.kernels import ops
from repro.kernels import quant_matmul as _qmm
from repro.models import Model
from repro.quant import QuantizedTensor, kv_group_size
from repro.serving import ServingEngine

MODELS = ("llama3.2-1b", "deepseek-7b", "mistral-nemo-12b")
BATCH = 4          # decode slots: the GEMV M and the attention batch
CACHE_LEN = 1024   # cached positions per slot
GROUP = 32
ROOT = Path(__file__).resolve().parents[1]
# attention kernels: one layer's cache, or the model's layer-stacked
# cache read by a scalar-prefetched layer index (all of deepseek-7b's or
# mistral-nemo-12b's 40 layers, GQA 4:1), at BATCH slots of CACHE_LEN;
# the last case at the solo cell's one slot of 8192 positions
ATTN = [("llama3.2-1b", False, BATCH, CACHE_LEN),
        ("deepseek-7b", False, BATCH, CACHE_LEN),
        ("deepseek-7b", True, BATCH, CACHE_LEN),
        ("mistral-nemo-12b", True, BATCH, CACHE_LEN),
        ("mistral-nemo-12b", True, 1, 8192)]
ATTN_IDS = ["llama3.2-1b", "deepseek-7b", "deepseek-7b-stacked",
            "mistral-nemo-12b-stacked", "mistral-nemo-12b-stacked-solo"]


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


def _operand_text(compiled):
    """The optimized HLO with each operand's shape printed beside it, as
    the op events of a chip trace carry them."""
    from jax._src.lib import xla_client as xc
    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(opts)


@pytest.mark.parametrize("arch", MODELS)
@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
def test_quant_matmul_compiles(arch, fmt, one_chip):
    rule = json.loads((ROOT / "perfbench" / "kernels.json").read_text())
    match = re.compile(rule["kernels"]["quant_matmul"]["match"])
    for K, N in _matmul_shapes(arch):
        bn = ops._pick_lane_tile(N, _qmm.DEFAULT_BN)
        bk = ops._quant_k_tile(K, fmt, GROUP)
        assert bn and bk, (K, N)     # the fused path, not the fallback
        rows = K // 2 if fmt == "q4_0" else K

        def f(x, data, scales):
            w = QuantizedTensor(data, scales, fmt, GROUP)
            return _qmm.quant_matmul(x, w, bm=BATCH, bn=bn, bk=bk)

        text = _operand_text(jax.jit(f).lower(
            jax.ShapeDtypeStruct((BATCH, K), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((rows, N), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((K // GROUP, N), jnp.bfloat16,
                                 sharding=one_chip)).compile())
        assert "tpu_custom_call" in text
        # one call of three operands, as the benchmark's rule reads it:
        # the activation bf16[M, K] beside the payload s8[K/2, N] (q4_0)
        # or s8[K, N] (q8_0)
        call = match.search(text)
        assert call, (K, N)
        shapes = re.findall(r"(bf16|s8)\[(\d+),(\d+)\]", call.group(0))
        assert shapes == [("bf16", str(BATCH), str(K)),
                          ("s8", str(rows), str(N)),
                          ("bf16", str(K // GROUP), str(N))], shapes


@pytest.mark.parametrize("arch,fmt,M", [("deepseek-7b", "q8_0", 8),
                                        ("mistral-nemo-12b", "q4_0", 1)])
def test_quant_matmul_lm_head_compiles(arch, fmt, M, one_chip):
    """The benchmark cells' quantized heads (vocabulary-wide N, f32
    logits) at their slot counts, with the tiles ``ops`` serves: one
    call the benchmark's rule matches, as a layer GEMV."""
    rule = json.loads((ROOT / "perfbench" / "kernels.json").read_text())
    match = re.compile(rule["kernels"]["quant_matmul"]["match"])
    cfg = get_config(arch)
    K, N = cfg.d_model, cfg.padded_vocab
    rows = K // 2 if fmt == "q4_0" else K
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    w = QuantizedTensor(
        jax.ShapeDtypeStruct((rows, N), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((K // GROUP, N), jnp.bfloat16,
                             sharding=one_chip), fmt, GROUP)
    bm, bn, bk = ops._quant_tiles(M, K, N, w)

    def f(x, w):
        return _qmm.quant_matmul(x, w, bm=bm, bn=bn, bk=bk,
                                 out_dtype=jnp.float32)

    text = _operand_text(jax.jit(f).lower(x, w).compile())
    call = match.search(text)
    assert call, (K, N)
    assert re.search(rf"f32\[{M},{N}\]\S* custom-call\(", text)


@pytest.mark.parametrize("K,N", [(64, 128), (128, 64), (640, 256)])
def test_quant_matmul_q4_0_full_span_compiles(K, N, one_chip):
    """Narrow q4_0 widths (the CPU suite's test models) take one
    full-span K block whose activation halves are not 128-aligned lane
    slices; Mosaic must still compile them, or those GEMVs would need
    the XLA fallback."""
    w_shape = QuantizedTensor(jnp.zeros((K // 2, N), jnp.int8),
                              jnp.zeros((K // GROUP, N), jnp.bfloat16),
                              "q4_0", GROUP)
    bm, bn, bk = ops._quant_tiles(BATCH, K, N, w_shape)
    assert bk == K and (bk // 2) % ops.LANE, bk    # the unaligned case

    def f(x, data, scales):
        w = QuantizedTensor(data, scales, "q4_0", GROUP)
        return _qmm.quant_matmul(x, w, bm=bm, bn=bn, bk=bk)

    text = _compiled_text(
        f,
        jax.ShapeDtypeStruct((BATCH, K), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((K // 2, N), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((K // GROUP, N), jnp.bfloat16,
                             sharding=one_chip))
    assert "tpu_custom_call" in text


def _attn_shapes(arch, one_chip, payload_dim=None, dtype=jnp.bfloat16,
                 stacked=False, batch=BATCH, cache_len=CACHE_LEN):
    """Query, one cache leaf, the lengths and the layer index, at the
    model's widths; a stacked leaf holds every layer's cache."""
    cfg = get_config(arch)
    B, Hq, Hkv, D = batch, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = (cfg.num_layers,) if stacked else ()
    q = jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(lead + (B, Hkv, cache_len, payload_dim or D),
                              dtype, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return q, kv, lens, layer


@pytest.mark.parametrize("arch,stacked,batch,cache_len", ATTN,
                         ids=ATTN_IDS)
def test_decode_attention_compiles(arch, stacked, batch, cache_len,
                                   one_chip):
    q, kv, lens, layer = _attn_shapes(arch, one_chip, stacked=stacked,
                                      batch=batch, cache_len=cache_len)
    bk = ops._pick_tile(cache_len, _da.DEFAULT_BK)
    text = _compiled_text(
        lambda q, k, v, n, l: _da.decode_attention(q, k, v, n, l, bk=bk),
        q, kv, kv, lens, layer)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch,stacked,batch,cache_len", ATTN,
                         ids=ATTN_IDS)
@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
def test_decode_attention_quant_compiles(arch, stacked, batch, cache_len,
                                         fmt, one_chip):
    D = get_config(arch).head_dim
    q, payload, lens, layer = _attn_shapes(
        arch, one_chip, D // 2 if fmt == "q4_0" else D, jnp.int8,
        stacked=stacked, batch=batch, cache_len=cache_len)
    groups = D // kv_group_size(D, GROUP, fmt)
    scales = jax.ShapeDtypeStruct(payload.shape[:-1] + (groups,),
                                  jnp.bfloat16, sharding=one_chip)
    bk = ops._pick_tile(cache_len, _daq.DEFAULT_BK)
    text = _compiled_text(
        lambda q, k, ks, v, vs, n, l: _daq.decode_attention_quant(
            q, k, ks, v, vs, n, l, fmt=fmt, bk=bk),
        q, payload, scales, payload, scales, lens, layer)
    assert "tpu_custom_call" in text


def test_megastep_keeps_the_cache_in_one_buffer(one_chip, monkeypatch):
    """The megastep's optimized HLO holds no copy of the whole stacked K
    or V cache, and no write into it (scatter or dynamic-update-slice)
    wider than one new row per slot: the cache rides the layer scan's
    carry, each substep writes only its new rows, and the attention
    kernel reads the stacked buffer by layer index (a layout the row
    writes disagree with shows up as copies; scanning the cache as
    xs/ys, as whole-layer writes)."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = reduced(get_config("deepseek-7b"), head_dim=128,
                  kernels="pallas")
    model = Model(cfg)
    eng = ServingEngine(model, model.abstract_params(), slots=BATCH,
                        max_len=256, megastep_k=4, donate_carries=True)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    args = [on_chip(a) for a in (eng.params, eng.cache, eng.state,
                                 eng._empty_admit())]
    text = eng._megastep.lower(*args, False).compile().as_text()
    assert "tpu_custom_call" in text
    shape = eng.cache["layers"]["k"].shape
    stacked = "bf16[%s]" % ",".join(map(str, shape))
    rows = shape[1] * shape[2] * shape[4]     # one new row per slot
    # instruction name -> (result type, opcode, operand names)
    hlo = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%\S+) = (.*?) ([\w-]+)\(([^)]*)\)",
                     line)
        if m:
            hlo[m.group(1)] = (m.group(2), m.group(3),
                               re.findall(r"%[\w.-]+", m.group(4)))

    def elements(name):
        dims = re.match(r"\w+\[([\d,]*)\]", hlo[name][0]).group(1)
        return int(np.prod([int(d) for d in dims.split(",") if d]))

    update = {"dynamic-update-slice": 1, "scatter": 2}  # its operand
    into = [(name, op, args) for name, (ty, op, args) in hlo.items()
            if stacked in ty]
    writes = [n for n, op, a in into if op in update]
    whole = [n for n, op, a in into
             if op in ("copy", "copy-start", "copy-done")
             or (op in update and elements(a[update[op]]) > rows)]
    assert not whole, whole
    assert writes, "no row write into the stacked cache found"


@pytest.mark.parametrize("slots,max_len", [(1, 8192), (BATCH, 2048)],
                         ids=["one-slot-8192", "4-slots-2048"])
def test_megastep_writes_quantized_cache_rows_in_place(one_chip, monkeypatch,
                                                       slots, max_len):
    """With a q8_0 cache the layer scan copies no stacked cache leaf,
    payload or scale: the scale leaves' rows (4 groups a head, narrower
    than a lane tile) are written in place, and the kernel reads them
    where they are. The program's entry may re-lay a scale leaf once a
    megastep, outside the scan. All 40 of mistral-nemo-12b's layers:
    XLA re-lays a scattered scale leaf only when it is this large."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = reduced(get_config("mistral-nemo-12b"), num_layers=40,
                  head_dim=128, kernels="pallas", quant_policy="q4_0",
                  kv_quant="q8_0")
    model = Model(cfg)
    eng = ServingEngine(model, model.abstract_params(), slots=slots,
                        max_len=max_len, megastep_k=4, donate_carries=True,
                        quant_policy="q4_0", kv_quant="q8_0")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    args = [on_chip(a) for a in (eng.params, eng.cache, eng.state,
                                 eng._empty_admit())]
    text = eng._megastep.lower(*args, True).compile().as_text()
    assert "tpu_custom_call" in text
    leaves = eng.cache["layers"]
    stacked = {"%s[%s]" % ({"int8": "s8", "bfloat16": "bf16"}[str(a.dtype)],
                           ",".join(map(str, a.shape)))
               for n, a in leaves.items() if n != "lens"}
    assert len(stacked) == 2               # payload and scale shapes
    copies, comp = [], None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%\S+ \(", line)
        if head:
            comp = "entry" if head.group(1) else "inner"
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])\S* "
                     r"(copy|copy-start)\(", line)
        if m and m.group(1) in stacked and comp == "inner":
            copies.append(line.strip()[:120])
    assert not copies, copies
