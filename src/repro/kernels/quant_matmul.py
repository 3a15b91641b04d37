"""W8A16 / W4A16 groupwise dequant-in-VMEM matmul (Pallas TPU).

The paper's profiling shows GEMM is 87.6%/76.2% of inference time and
that Q4 quantization is the single largest lever (§5.3). On TPU the
equivalent design is: keep weights in HBM at 4.5/8.5 bits, stream the
*quantized* blocks into VMEM, dequantize there (VREG shifts + one
multiply per group) and feed the MXU with bf16 tiles. HBM traffic drops
by the quantization ratio — exactly the memory-roofline win the paper
measures on the A17's DRAM bus.

Tiling: grid (M/bm, N/bn, K/bk), K innermost so the f32 accumulator
tile lives in VMEM scratch across the K loop. ``bk`` must be a multiple
of the quant group (32). Lane alignment is *not* assumed here: the
dispatch layer (``ops.matmul`` → ``_quant_tiles``) enforces that the
lane dims bn/bk are 128-aligned or span their whole dimension, and the
sublane dim bm is 8-aligned when M >= 8 (bm = M below that — Mosaic
pads sublanes for small decode GEMVs); shapes with no such tiling fall
back to the XLA dequant path instead of reaching this kernel.

q4_0 without an interleave: the stored format packs logical row 2i in
the low nibble of packed row i and row 2i+1 in the high one
(``pack_int4``; unchanged here), so

    x @ W  =  x[:, 0::2] @ lo  +  x[:, 1::2] @ hi.

A 32-row quant group is 16 packed rows of each plane under the same
scale row, so each plane dequantizes where it lies with the scales as
stored. The wrapper reorders the activation once instead of the weight:
inside every K-block of ``bk`` columns the even columns come first, then
the odd ones, so the kernel pairs the first ``bk/2`` columns of its
activation block with the low plane and the last ``bk/2`` with the high
plane — two dots into one f32 accumulator. The call keeps its three
operands (``bf16[M, K]``, ``s8[K/2, N]``, the scales). The half-block
activation slice is a lane slice, free where ``bk/2`` is a multiple of
128; ``ops._quant_k_tile`` holds the q4_0 tile rule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.quantize import QuantizedTensor, int4_nibbles

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _dequant_rows(qblk, sblk, rows):
    """Scale each run of ``rows`` rows of an integer tile by its row of
    ``sblk``, in f32."""
    bk, bn = qblk.shape
    q = qblk.astype(jnp.float32).reshape(bk // rows, rows, bn)
    return (q * sblk.astype(jnp.float32)[:, None, :]).reshape(bk, bn)


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, fmt: str,
                group: int, k_steps: int, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    # Round the dequantized tile to the activation dtype and feed the
    # MXU an activation-dtype x activation-dtype dot with f32
    # accumulation — the op ops.matmul's XLA fallback runs on
    # dequantize(w, out_dtype), on the same rounded operands where the
    # output is the activation dtype (an f32 lm_head's fallback keeps
    # f32 weights). Only the f32 summation order over K differs (per
    # block, and for q4_0 per half block), so a bf16 output can differ
    # from the fallback's by one rounding step, rarely.
    if fmt == "q8_0":
        w = _dequant_rows(q_ref[...], s_ref[...], group)
        acc_ref[...] += jax.lax.dot(x, w.astype(x.dtype),
                                    preferred_element_type=jnp.float32)
    else:
        # Each nibble plane meets its own half of the reordered block.
        lo, hi = int4_nibbles(q_ref[...])
        s = s_ref[...]
        h = lo.shape[0]
        lo_w = _dequant_rows(lo, s, group // 2).astype(x.dtype)
        hi_w = _dequant_rows(hi, s, group // 2).astype(x.dtype)
        acc_ref[...] += (
            jax.lax.dot(x[:, :h], lo_w, preferred_element_type=jnp.float32)
            + jax.lax.dot(x[:, h:], hi_w,
                          preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def quant_matmul(x: jax.Array, w: QuantizedTensor, *,
                 bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                 bk: int = DEFAULT_BK,
                 out_dtype=jnp.bfloat16,
                 interpret: bool = False) -> jax.Array:
    """``x @ dequant(w)`` with in-kernel dequantization.

    x: (M, K) activation; w: logical (K, N) in q8_0 (data (K, N) int8)
    or q4_0 (data (K//2, N) packed int8); scales (K//group, N).
    """
    M, K = x.shape
    Kw, N = w.logical_shape[-2:]
    if K != Kw:
        raise ValueError(
            f"quant_matmul: reduction-dim mismatch — x has K={K} "
            f"(shape {x.shape}) but weight has K={Kw} "
            f"(logical shape {w.logical_shape})")
    group = w.group
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    if K % bk or bk % group:
        raise ValueError(
            f"quant_matmul: bk={bk} must divide K={K} and be a multiple "
            f"of the quant group {group} (x {x.shape}, w "
            f"{w.logical_shape} {w.fmt})")
    if M % bm or N % bn:
        raise ValueError(
            f"quant_matmul: tiles bm={bm}, bn={bn} must divide "
            f"M={M}, N={N} (x {x.shape}, w {w.logical_shape} {w.fmt})")
    k_steps = K // bk
    packed = w.fmt == "q4_0"
    kdiv = 2 if packed else 1
    if packed:
        # even columns of each K-block first, then its odd ones
        x = x.reshape(M, k_steps, bk // 2, 2).swapaxes(-1, -2).reshape(M, K)

    kernel = functools.partial(
        _qmm_kernel, fmt=w.fmt, group=group, k_steps=k_steps,
        out_dtype=out_dtype)

    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // kdiv, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(x, w.data, w.scales)
