"""Groupwise weight quantization — llama.cpp k-quant analogues.

Formats (paper §4.2):
- ``q8_0``: groups of 32 along the reduction dim; int8 payload + one
  f16-ish scale per group → 8.5 bits/weight.
- ``q4_0``: groups of 32; symmetric int4 in [-8, 7], two nibbles packed
  per int8 byte → 4.5 bits/weight (the paper's footnote).

A ``QuantizedTensor`` is a pytree (works inside jit / pjit / scan), so
quantized models shard and checkpoint exactly like bf16 ones. The
packed layout matches what ``kernels/quant_matmul.py`` consumes: the
reduction dim K is the second-to-last axis, scales have shape
``K//group`` on that axis.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Groupwise-quantized 2-D (or stacked 3-D) weight.

    data:   int8. q8_0 → shape (..., K, N); q4_0 → (..., K//2, N) packed.
    scales: activation-dtype, shape (..., K//group, N broadcast? no:
            (..., K//group, N)) — per (group, column) scale, llama.cpp
            row-major k-quant transposed to column-major matmul layout.

    The logical (unquantized) shape is *derived* from the live ``data``
    array, never stored: a stacked (L, K, N) weight that rides through a
    scan-over-layers loses its leading dim on the pytree children each
    iteration, and any statically-stored shape would go stale (jit
    transforms carry aux data through unchanged). ``shape`` /
    ``logical_shape`` therefore always describe the tensor as it is now.
    """
    data: jax.Array
    scales: jax.Array
    fmt: str            # "q8_0" | "q4_0"
    group: int = 32

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.scales), (self.fmt, self.group)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scales = children
        fmt, group = aux
        return cls(data, scales, fmt, group)

    @property
    def dtype(self):
        return self.scales.dtype

    @property
    def logical_shape(self) -> Tuple[int, ...]:
        """Logical (unquantized) shape ``(..., K, N)`` derived from the
        *current* data array — authoritative under any slicing (scan
        over stacked layers, vmap, manual ``data[i]`` indexing)."""
        k2 = self.data.shape[-2]
        K = 2 * k2 if self.fmt == "q4_0" else k2
        return tuple(self.data.shape[:-2]) + (K, self.data.shape[-1])

    @property
    def shape(self) -> Tuple[int, ...]:
        """Alias of :attr:`logical_shape` (ndarray-duck-typed)."""
        return self.logical_shape

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def k_axis(self) -> int:
        return self.data.ndim - 2

    @property
    def logical_nbytes(self) -> int:
        import numpy as np
        return int(np.prod(self.logical_shape)) * 2

    @property
    def quant_nbytes(self) -> int:
        return self.data.size * self.data.dtype.itemsize + \
            self.scales.size * self.scales.dtype.itemsize


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int4 values in [-8,7] pairwise along axis -2 into int8.

    Element (2i, n) goes to the low nibble of packed (i, n); (2i+1, n)
    to the high nibble.
    """
    assert q.shape[-2] % 2 == 0, q.shape
    lo = q[..., 0::2, :] & 0x0F
    hi = q[..., 1::2, :] & 0x0F
    return (lo | (hi << 4)).astype(jnp.int8)


def int4_nibbles(packed: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sign-extended (low, high) nibbles of packed int8 bytes, as int32.

    The shifts run on 32-bit lanes: Mosaic cannot legalize an int8
    arithmetic right shift, and the Pallas kernels share this helper.
    """
    q = packed.astype(jnp.int32)
    return (q << 28) >> 28, (q << 24) >> 28


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4` → int8 values in [-8, 7]."""
    lo, hi = int4_nibbles(packed)
    k2 = packed.shape[-2]
    out_shape = packed.shape[:-2] + (2 * k2,) + packed.shape[-1:]
    # interleave: stack -> (..., k2, 2, n), row-major reshape -> (..., 2*k2, n)
    out = jnp.stack([lo, hi], axis=-2)
    return out.reshape(out_shape).astype(jnp.int8)


def _group_scales(w: jax.Array, group: int, qmax: float):
    *lead, K, N = w.shape
    assert K % group == 0, (K, group)
    wg = w.reshape(*lead, K // group, group, N)
    amax = jnp.max(jnp.abs(wg), axis=-2)          # (..., K//group, N)
    scale = (amax / qmax).astype(jnp.float32)
    scale = jnp.where(scale == 0, 1.0, scale)
    return wg, scale


def quantize_q8_0(w: jax.Array, group: int = 32) -> QuantizedTensor:
    wg, scale = _group_scales(w.astype(jnp.float32), group, 127.0)
    q = jnp.clip(jnp.round(wg / scale[..., None, :]), -127, 127)
    q = q.astype(jnp.int8).reshape(w.shape)
    return QuantizedTensor(q, scale.astype(jnp.bfloat16), "q8_0", group)


def quantize_q4_0(w: jax.Array, group: int = 32) -> QuantizedTensor:
    wg, scale = _group_scales(w.astype(jnp.float32), group, 7.0)
    q = jnp.clip(jnp.round(wg / scale[..., None, :]), -8, 7)
    q = q.astype(jnp.int8).reshape(w.shape)
    return QuantizedTensor(pack_int4(q), scale.astype(jnp.bfloat16),
                           "q4_0", group)


def quantize(w: jax.Array, fmt: str, group: int = 32):
    if fmt in ("bf16", "f16", "f32"):
        return w
    if fmt == "q8_0":
        return quantize_q8_0(w, group)
    if fmt == "q4_0":
        return quantize_q4_0(w, group)
    raise ValueError(fmt)


def dequantize(qt: QuantizedTensor, dtype=jnp.bfloat16) -> jax.Array:
    if qt.fmt == "q8_0":
        q = qt.data
    elif qt.fmt == "q4_0":
        q = unpack_int4(qt.data)
    else:
        raise ValueError(qt.fmt)
    *lead, K, N = qt.logical_shape
    qg = q.reshape(*lead, K // qt.group, qt.group, N).astype(jnp.float32)
    w = qg * qt.scales[..., None, :].astype(jnp.float32)
    return w.reshape(*lead, K, N).astype(dtype)


# ---------------------------------------------------------------------------
# Row-wise (last-axis) groupwise quantization — KV-cache leaves
# ---------------------------------------------------------------------------
#
# Weights quantize along the reduction dim (axis -2, the matmul K); KV
# cache entries quantize along the *feature* dim (axis -1, head_dim):
# each cached position is written once and read many times, so the
# scale must be local to the row being written — a plain (payload,
# scales) array pair rather than a QuantizedTensor, because the two
# arrays live as sibling leaves of the cache pytree (k / k_scale) and
# ride scan / donate_argnums / cache_axes splicing like any other leaf.
# All helpers are pure jnp and shape-static: callable from inside jit
# (the cache-write point in ``decode_step`` / ``prefill``).

def kv_group_size(dim: int, group: int, fmt: str) -> int:
    """Effective group size for quantizing a ``dim``-wide row: the
    largest divisor of ``dim`` that is <= ``group`` (head dims are not
    always multiples of 32). q4_0 additionally needs ``dim`` even to
    nibble-pack pairs along the row."""
    if fmt == "q4_0" and dim % 2:
        raise ValueError(
            f"q4_0 KV rows need an even dim to pack nibbles (got {dim})")
    g = min(group, dim)
    while dim % g:
        g -= 1
    return g


def pack_int4_rows(q: jax.Array) -> jax.Array:
    """Pack int4 values in [-8, 7] along the LAST axis, half-split.

    Byte j of a d-wide row holds feature j in its low nibble and
    feature j + d/2 in its high one, so unpacking is a lane
    concatenation of the two halves. (An even/odd pair layout needs a
    lane interleave, which Mosaic refuses at head dim 128.)
    """
    assert q.shape[-1] % 2 == 0, q.shape
    h = q.shape[-1] // 2
    lo = q[..., :h] & 0x0F
    hi = q[..., h:] & 0x0F
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4_rows(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4_rows` → int8 values in [-8, 7]."""
    return jnp.concatenate(int4_nibbles(packed), axis=-1).astype(jnp.int8)


def quantize_rows(x: jax.Array, fmt: str, group: int = 32
                  ) -> Tuple[jax.Array, jax.Array]:
    """Groupwise-quantize along the last axis.

    x: (..., d) → (payload int8 (..., d) [q8_0] or (..., d//2) [q4_0],
    scales bf16 (..., d // g)) with ``g = kv_group_size(d, group, fmt)``.

    Determinism note: bf16 inputs are dyadic, so ``x / scale`` lands on
    exact .5 ties often (the group max maps to qmax exactly). XLA-CPU's
    compiled division (reciprocal-multiply under fast-math) can break
    such ties one ulp differently from the eager op — so compare
    quantized payloads *within* one compilation regime (the serving
    engine and ``reference_decode`` are both jitted, which is why their
    cache leaves match bit-exactly; an eager recomputation may differ
    by one quantization step on tie elements).
    """
    d = x.shape[-1]
    g = kv_group_size(d, group, fmt)
    qmax = 127.0 if fmt == "q8_0" else 7.0
    if fmt not in ("q8_0", "q4_0"):
        raise ValueError(fmt)
    xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // g, g))
    amax = jnp.max(jnp.abs(xg), axis=-1)
    scale = (amax / qmax).astype(jnp.float32)
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xg / scale[..., None]), -qmax, qmax)
    q = q.astype(jnp.int8).reshape(x.shape)
    if fmt == "q4_0":
        q = pack_int4_rows(q)
    return q, scale.astype(jnp.bfloat16)


def dequantize_rows(payload: jax.Array, scales: jax.Array, fmt: str,
                    dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`quantize_rows` (group size inferred from the
    scales' last dim)."""
    if fmt == "q4_0":
        q = unpack_int4_rows(payload)
    elif fmt == "q8_0":
        q = payload
    else:
        raise ValueError(fmt)
    d = q.shape[-1]
    g = d // scales.shape[-1]
    qg = q.reshape(q.shape[:-1] + (d // g, g)).astype(jnp.float32)
    x = qg * scales[..., None].astype(jnp.float32)
    return x.reshape(q.shape).astype(dtype)


def quantize_tree(params, fmt: str, group: int = 32,
                  predicate=None):
    """Quantize every matmul weight in a param pytree.

    Default selection: leaves with ndim >= 2 and K (axis -2) divisible
    by ``group``, *excluding* any path containing ``norm`` or ``embed``.
    Norm scales/biases are sub-2-D or precision-critical; embedding
    tables (and the tied ``lm_head`` when it shares the ``embed`` path)
    are read row-wise by gather, not streamed through ``quant_matmul``'s
    K-major tiling, so they stay bf16. Pass ``predicate(path, leaf) ->
    bool`` to additionally restrict the selection (it cannot re-enable
    a skipped path; tests/test_quant.py pins exactly which leaves of a
    dense model quantize).
    """
    if fmt in ("bf16", "f16", "f32"):
        return params

    def maybe_quant(path, leaf):
        if isinstance(leaf, QuantizedTensor):
            return leaf
        pred_ok = predicate is None or predicate(path, leaf)
        path_str = jax.tree_util.keystr(path)
        is_weight = (getattr(leaf, "ndim", 0) >= 2
                     and leaf.shape[-2] % group == 0
                     and "embed" not in path_str
                     and "norm" not in path_str)
        if pred_ok and is_weight:
            return quantize(leaf, fmt, group)
        return leaf

    # is_leaf stops traversal AT QuantizedTensor nodes: without it,
    # tree_map descends into their (data, scales) children and
    # re-quantizes the int8 payload itself — the idempotency the
    # isinstance() check above promises would silently never trigger
    return jax.tree_util.tree_map_with_path(
        maybe_quant, params,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
