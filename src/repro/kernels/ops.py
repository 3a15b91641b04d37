"""Public jit'd kernel wrappers — the dispatch point between Pallas and XLA.

Models call these with ``use_pallas`` read from ``cfg.kernels``
("pallas" or "xla"): that switch decides whether the Pallas path runs.
Off a TPU, Pallas executes in interpret mode; on a TPU the same call
sites always compile to Mosaic (see ``_interpret_default``).
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.quant.quantize import QuantizedTensor, dequantize, dequantize_rows
from repro.kernels import quant_matmul as _qmm
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import decode_attention_quant as _daq

# MXU/VREG lane width: the minor tile dim of any Mosaic-compiled
# operand must be a multiple of this (sublane dims only need 8).
LANE = 128
SUBLANE = 8


def _interpret_default() -> bool:
    """Interpret mode off a TPU only: on a TPU backend the kernels always
    compile to Mosaic, whatever ``REPRO_PALLAS_INTERPRET`` says."""
    if jax.default_backend() == "tpu":
        return False
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "off", "")
    return True


def matmul(x: jax.Array, w: Union[jax.Array, QuantizedTensor], *,
           use_pallas: bool = False,
           out_dtype=None) -> jax.Array:
    """x @ w for plain or quantized weights.

    x may have leading batch dims; they are flattened into M. Without
    ``use_pallas``, quantized weights dequantize via XLA (still saves
    HBM for storage; in-kernel dequant needs the Pallas path).
    """
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K = x.shape[-1]
    if isinstance(w, QuantizedTensor):
        N = w.logical_shape[-1]
        if use_pallas:
            x2 = x.reshape(-1, K)
            tiles = _quant_tiles(x2.shape[0], K, N, w)
            if tiles:
                bm, bn, bk = tiles
                out = _qmm.quant_matmul(
                    x2, w, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                    interpret=_interpret_default())
                return out.reshape(*lead, N)
        wd = dequantize(w, out_dtype)
        # Barrier: pin the dot's operands to their materialized
        # activation-dtype values. Inside a fused jit graph XLA-CPU
        # otherwise feeds the dot *unrounded* f32 activations (the
        # bf16 cast upstream is elided as excess precision), while the
        # Pallas path always reads rounded bf16 through the
        # pallas_call boundary — the two backends would then disagree
        # at the last ulp and greedy token streams could flip. Same
        # trick as _decode_attention_jnp's cache barrier below.
        x, wd = jax.lax.optimization_barrier((x, wd))
        return jnp.dot(x, wd, preferred_element_type=jnp.float32
                       ).astype(out_dtype)
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(out_dtype)


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def _pick_tile(dim: int, preferred: int, multiple: int = 1) -> Optional[int]:
    """Largest tile <= preferred that divides dim and is a multiple of
    ``multiple``. Returns None when no such tile exists — callers fall
    back to the XLA path rather than hand Mosaic a misaligned tile
    (e.g. bn=29 for a prime-factor dim, which only "works" in interpret
    mode)."""
    t = (min(preferred, dim) // multiple) * multiple
    while t >= multiple:
        if dim % t == 0:
            return t
        t -= multiple
    return None


def _pick_lane_tile(dim: int, preferred: int,
                    multiple: int = 1) -> Optional[int]:
    """Tile for a 128-lane minor dim: a 128-aligned divisor, or the
    full dim when it fits in one 8-aligned block (Mosaic pads a
    full-span minor dim to the lane width; it cannot *partition* a dim
    into misaligned tiles). None → XLA fallback."""
    t = _pick_tile(dim, preferred, multiple=_lcm(multiple, LANE))
    if t:
        return t
    if dim <= preferred and dim % _lcm(multiple, SUBLANE) == 0:
        return dim          # single full-span block, lane-padded
    return None


def _quant_tiles(M: int, K: int, N: int,
                 w: QuantizedTensor) -> Optional[tuple]:
    """``quant_matmul``'s (bm, bn, bk) for an (M, K) @ w GEMM, or None:
    the XLA fallback. Tile sizes must divide, and the lane dims (bn, and
    bk — the minor dim of the activation block) must be 128-aligned or
    span their whole dim, else Mosaic won't compile them. bm is the
    sublane dim: 8-aligned when M allows, else bm = M (< 8) and Mosaic
    pads the sublanes — that keeps M=1..7 decode GEMVs on the fused path
    instead of the old degenerate bm=1 tiling of large M."""
    bm = M if M < SUBLANE else _pick_tile(M, _qmm.DEFAULT_BM,
                                          multiple=SUBLANE)
    bn = _pick_lane_tile(N, _qmm.DEFAULT_BN)
    bk = _quant_k_tile(K, w.fmt, w.group)
    return (bm, bn, bk) if bm and bn and bk else None


# q4_0's preferred K tile: its packed block (bk/2, bn) is then q8_0's
# 128 KiB. On a v5e a q4_0 step at M = 1 costs ~0.26 µs fixed + ~0.15 µs
# per 512 rows, so the longer tile streams the weights ~1.45x faster.
DEFAULT_BK_Q4 = 1024


def _quant_k_tile(K: int, fmt: str, group: int) -> Optional[int]:
    """``quant_matmul``'s K tile: whole quant groups, lane-aligned. The
    q4_0 kernel slices each activation block in half along its lanes. A
    tile that partitions K holds whole 8-row blocks of scales, so it is
    a multiple of 256 (at group 32) and its half is 128-aligned: a free
    slice. Else q4_0 takes one full-span block, as narrow widths do;
    Mosaic shifts the lanes of its unaligned half. It prefers the longer
    tile that packs as many bytes as q8_0's."""
    if fmt == "q4_0":
        t = _pick_tile(K, DEFAULT_BK_Q4, multiple=_lcm(group, 2 * LANE))
        if t is None and K <= DEFAULT_BK_Q4 \
                and K % _lcm(group, SUBLANE) == 0:
            t = K
        return t
    return _pick_lane_tile(K, _qmm.DEFAULT_BK, multiple=group)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0,
              q_offset: int = 0, use_pallas: bool = False,
              scale: Optional[float] = None) -> jax.Array:
    """Prefill/training attention; (B, H, S, D) layout."""
    if use_pallas:
        Sq, Skv = q.shape[2], k.shape[2]
        bq = _pick_tile(Sq, _fa.DEFAULT_BQ)
        bk = _pick_tile(Skv, _fa.DEFAULT_BK)
        if bq and bk:
            return _fa.flash_attention(
                q, k, v, causal=causal, window=window, scale=scale,
                q_offset=q_offset, bq=bq, bk=bk,
                interpret=_interpret_default())
    from repro.kernels import ref
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale, q_offset=q_offset)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, kv_len, *,
                     layer=None, window: int = 0, use_pallas: bool = False,
                     scale: Optional[float] = None) -> jax.Array:
    """Single-token attention; q (B, H, D), cache (B, Hkv, S, D), or the
    layer-stacked (L, B, Hkv, S, D) with ``layer`` naming the layer.

    The Pallas kernel reads the stacked cache in place (the layer picks
    its blocks); the XLA fallback indexes the layer itself."""
    if use_pallas:
        S = k.shape[-2]
        bk = _pick_tile(S, _da.DEFAULT_BK)
        if bk:
            return _da.decode_attention(
                q, k, v, kv_len, 0 if layer is None else layer,
                window=window, scale=scale, bk=bk,
                interpret=_interpret_default())
    if layer is not None:
        k, v = k[layer], v[layer]
    return _decode_attention_jnp(q, k, v, kv_len, window=window,
                                 scale=scale)


def decode_attention_quant(q: jax.Array, k_q: jax.Array,
                           k_scale: jax.Array, v_q: jax.Array,
                           v_scale: jax.Array, kv_len, *, fmt: str,
                           layer=None, window: int = 0,
                           use_pallas: bool = False,
                           scale: Optional[float] = None) -> jax.Array:
    """Decode attention straight off quantized KV-cache leaves.

    q: (B, Hq, D); k_q/v_q int8 payload (B, Hkv, S, D) [q8_0] or
    (B, Hkv, S, D//2) [q4_0]; k_scale/v_scale (B, Hkv, S, D//g); or
    all four layer-stacked with a leading L and ``layer`` naming the
    layer.

    The Pallas path dequantizes in-register inside the online-softmax
    block loop — HBM reads stay at the quantized width. The XLA
    fallback is computation-identical to the pre-fusion production
    path: materialize a bf16 view (``dequantize_rows``) and run
    ``_decode_attention_jnp`` on it.
    """
    if use_pallas:
        S = k_q.shape[-2]
        bk = _pick_tile(S, _daq.DEFAULT_BK)
        if bk:
            return _daq.decode_attention_quant(
                q, k_q, k_scale, v_q, v_scale, kv_len,
                0 if layer is None else layer, fmt=fmt,
                window=window, scale=scale, bk=bk,
                interpret=_interpret_default())
    if layer is not None:
        k_q, k_scale, v_q, v_scale = (
            a[layer] for a in (k_q, k_scale, v_q, v_scale))
    k = dequantize_rows(k_q, k_scale, fmt)
    v = dequantize_rows(v_q, v_scale, fmt)
    return _decode_attention_jnp(q, k, v, kv_len, window=window,
                                 scale=scale)


def _decode_attention_jnp(q, k, v, kv_len, *, window: int = 0,
                          scale: Optional[float] = None) -> jax.Array:
    """bf16-preserving decode attention (the XLA production path).

    Deliberately avoids ``k.astype(f32)`` / ``v.astype(f32)``: inside a
    scan-over-layers, XLA hoists such elementwise converts out of the
    loop, materializing an f32 copy of the *entire stacked KV cache*
    (2x the cache in HBM). Mixed-precision matmuls with
    ``preferred_element_type=f32`` keep the cache read at bf16 width.
    """
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    kv_len = jnp.asarray(kv_len)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len)
    # Barrier: stops XLA-CPU's bf16→f32 dot legalization converts from
    # being loop-hoisted over the whole stacked cache (2x HBM). No-op
    # on TPU where bf16 dots are native.
    k, v = jax.lax.optimization_barrier((k, v))
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k,
                   preferred_element_type=jnp.float32)    # (B,Hkv,G,S)
    kpos = jnp.arange(S)[None, :]
    mask = kpos < kv_len[:, None]
    if window:
        mask &= kpos >= kv_len[:, None] - window
    s = jnp.where(mask[:, None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask[:, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bhkd->bhgd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-20)
    return out.reshape(B, Hq, D).astype(q.dtype)
