"""Flash-decoding attention over a *quantized* KV cache (fused dequant).

PR 4 measured the q4_0 failure mode the paper's Fig 4e predicts:
streaming 0.281x the cache bytes but decoding at 0.75-0.81x bf16,
because ``kv_cache_read`` materializes a full dequantized bf16 cache
view every megastep — the dequant-bandwidth tax that dominates low-bit
formats on memory-bound decode. This kernel eliminates the unpack: it
reads the int8 payload + groupwise scales leaves directly and
dequantizes in-register inside the online-softmax block loop, so HBM
traffic stays at the quantized width (8.5/16 or 4.5/16 of bf16) and the
unpack cost is VREG shifts hidden under the cache stream.

Same grid and scratch layout as ``decode_attention.py``:
(B, Hkv, S/bk), online-softmax (m, l, acc) state in VMEM scratch,
grouped queries (G, D) per KV head. The q4_0 nibble-unpack
(shift/sign-extend) is fused into the K/V block load; dequantized
values are rounded to bf16 before the dot so the kernel feeds the MXU
the exact values the XLA path (``dequantize_rows`` -> bf16 view) sees.

Payload layouts (see quant/quantize.py row-wise helpers):
  q8_0: k/v (B, Hkv, S, D) int8;      scales (B, Hkv, S, D//g) bf16
  q4_0: k/v (B, Hkv, S, D//2) int8 (two nibbles per byte: byte j
        holds feature j low and feature j + D/2 high); scales as
        above. g = kv_group_size(D, group, fmt) — inferred here from
        the scales' last dim, so non-group-aligned head dims (any
        divisor group) just work.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.quantize import int4_nibbles

DEFAULT_BK = 1024
NEG_INF = -1e30


def _dequant_rows(qblk: jax.Array, sblk: jax.Array, fmt: str) -> jax.Array:
    """In-register row-wise dequant of one (bk, D[/2]) cache block.

    Mirrors ``quant.quantize.dequantize_rows`` (incl. the bf16 rounding
    of its default out dtype) so the kernel is value-identical to the
    XLA unpack path; the dots below run on these bf16 values with f32
    accumulation, the same op the XLA oracle runs.
    """
    if fmt == "q4_0":
        # half-split rows: low nibbles are features [0, D/2), high
        # nibbles [D/2, D) — a lane concat, never a lane interleave
        q = jnp.concatenate(int4_nibbles(qblk), axis=-1)
    else:
        q = qblk
    # expand the (bk, D//g) scales along lanes instead of splitting the
    # feature lanes into (D//g, g) groups, a shape cast Mosaic refuses
    g = q.shape[-1] // sblk.shape[-1]
    s = jnp.repeat(sblk.astype(jnp.float32), g, axis=-1)
    return (q.astype(jnp.float32) * s).astype(jnp.bfloat16)


def _decode_quant_kernel(lens_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, fmt: str,
                         scale: float, window: int, bk: int,
                         kv_steps: int, out_dtype):
    b, j = pl.program_id(0), pl.program_id(2)
    kv_len = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo_valid = kv_len - window if window else 0
    blk_visible = jnp.logical_and(j * bk < kv_len,
                                  (j + 1) * bk > lo_valid)

    @pl.when(blk_visible)
    def _body():
        # Scaled q and p round to the input dtype, and the dots run on
        # input-dtype operands with f32 accumulation — exactly the ops
        # the XLA oracle (ops._decode_attention_jnp on a dequantized
        # bf16 view) runs, so bf16 serving is token-identical across
        # backends; no-ops for f32 inputs.
        q = (q_ref[0, 0].astype(jnp.float32) * scale
             ).astype(q_ref.dtype)                           # (G, D)
        k = _dequant_rows(kq_ref[0, 0], ks_ref[0, 0], fmt
                          ).astype(q_ref.dtype)              # (bk, D)
        v = _dequant_rows(vq_ref[0, 0], vs_ref[0, 0], fmt
                          ).astype(q_ref.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if window:
            mask &= kpos >= kv_len - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(q_ref.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == kv_steps - 1)
    def _store():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(out_dtype)


def decode_attention_quant(q: jax.Array, k_q: jax.Array, k_scale: jax.Array,
                           v_q: jax.Array, v_scale: jax.Array, kv_len, *,
                           fmt: str, window: int = 0,
                           scale: Optional[float] = None,
                           bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); k_q/v_q int8 payload (B, Hkv, S, D or D//2);
    k_scale/v_scale (B, Hkv, S, D//g); kv_len: (B,) int32."""
    if fmt not in ("q8_0", "q4_0"):
        raise ValueError(f"decode_attention_quant: fmt must be q8_0 or "
                         f"q4_0, got {fmt!r}")
    B, Hq, D = q.shape
    _, Hkv, S, Dp = k_q.shape
    if (D // 2 if fmt == "q4_0" else D) != Dp:
        raise ValueError(f"payload dim {Dp} inconsistent with head dim "
                         f"{D} under {fmt} (q {q.shape}, k_q {k_q.shape})")
    assert Hq % Hkv == 0
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bk = min(bk, S)
    assert S % bk == 0
    kv_steps = S // bk
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len, jnp.int32)

    ng = k_scale.shape[-1]
    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(
        _decode_quant_kernel, fmt=fmt, scale=scale, window=window, bk=bk,
        kv_steps=kv_steps, out_dtype=q.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, kv_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # kv_len
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, ng), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, ng), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, qg, k_q, k_scale, v_q, v_scale)
    return out.reshape(B, Hq, D)
