"""Flash-decoding style single-token GQA attention over a KV cache.

The decode phase is the paper's primary regime (batch-1 token
generation, §5): one query token attends to a long cache. On TPU the
cache read is the memory-roofline term, so the kernel streams KV blocks
through VMEM once, keeping the (m, l, acc) online-softmax state in
scratch. The grouped queries for one KV head — shape (G, D), where
G = Hq/Hkv — are processed together, so K/V blocks are read exactly
once per KV head (GQA's entire point, paper §2.1).

Supports part-filled caches (kv_len per batch row) and sliding-window
caches (only the last ``window`` entries are valid, ring-buffer order
handled by the caller via kv_len masking).

The cache may be the model's layer-stacked (L, B, Hkv, S, D) buffer:
the layer index is scalar-prefetched and the K/V ``index_map``s select
that layer's blocks, so the decode layer scan hands the kernel the
whole stacked cache and nothing is sliced out (a custom call's operand
is a materialized buffer; a slice would copy the layer's cache).

Grid: (B, Hkv, S/bk). Block-skip for entries beyond kv_len.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 1024
NEG_INF = -1e30


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, window: int,
                   bk: int, kv_steps: int, out_dtype):
    b, j = pl.program_id(0), pl.program_id(2)
    kv_len = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip blocks entirely past the valid region / before the window
    lo_valid = kv_len - window if window else 0
    blk_visible = jnp.logical_and(j * bk < kv_len,
                                  (j + 1) * bk > lo_valid)

    @pl.when(blk_visible)
    def _body():
        # Mirror the XLA oracle (ops._decode_attention_jnp): the scaled
        # query and the probabilities round back to the input dtype,
        # and the dots run on input-dtype operands with f32
        # accumulation — so bf16 serving runs are token-identical
        # across kernel backends (all no-ops for f32 inputs).
        q = (q_ref[0, 0].astype(jnp.float32) * scale
             ).astype(q_ref.dtype)                       # (G, D)
        k = k_ref[0, 0].astype(q_ref.dtype)              # (bk, D)
        v = v_ref[0, 0].astype(q_ref.dtype)
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


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len, layer=0, *, window: int = 0,
                     scale: Optional[float] = None,
                     bk: int = DEFAULT_BK,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); k, v: (B, Hkv, S, D), or the layer-stacked
    (L, B, Hkv, S, D) with ``layer`` (int32 scalar) naming the layer
    read; kv_len: (B,) int32."""
    if k.ndim == 4:
        k, v = k[None], v[None]
    B, Hq, D = q.shape
    _, _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bk = min(bk, S)
    assert S % bk == 0
    kv_steps = S // bk
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len, jnp.int32)
    # one scalar-prefetch array: the B lengths, then the layer
    lens = jnp.concatenate(
        [kv_len, jnp.asarray(layer, jnp.int32).reshape(1)])

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, bk=bk,
        kv_steps=kv_steps, out_dtype=q.dtype)
    kv_spec = pl.BlockSpec((pl.squeezed, 1, 1, bk, D),
                           lambda b, h, j, n: (n[B], b, h, j, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, kv_steps),
            in_specs=[
                pl.BlockSpec((1, 1, G, D),
                             lambda b, h, j, n: (b, h, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, j, n: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lens, qg, k, v)
    return out.reshape(B, Hq, D)
