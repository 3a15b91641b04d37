"""Seeded weights, made on the device in the form they are served in.

One jitted call makes the whole model from the seed: a scan over the
layers draws one layer's float32 weights at a time and quantizes them
to the configuration's format (groups of 32 along the reduction axis,
int8 payload and one bf16 scale per group and column; q4_0 packs rows
2i and 2i+1 into the low and high nibble of one byte). No bf16 or f32
copy of the model exists at any time; the largest temporary is one
layer's float32 slice, or one eighth of the embedding.

The layout is this benchmark's own and independent of the program:
per layer ``wqkv`` (D, q|k|v), ``wo``, ``w_gate_up`` (D, gate|up),
``w_down``, ``attn_norm``, ``ffn_norm``, stacked over layers; then
``embedding`` (V, D) bf16, ``lm_head`` (D, V) quantized, ``final_norm``.
Matrices are normal with standard deviation ``K ** -0.5``, the
embedding ``D ** -0.5``; norm weights are ``1 + 0.1 * normal``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import work

# fixed leaf ids: fold_in(seed key, id) gives each leaf its own stream
_LEAF_IDS = {"layers": 1, "embedding": 2, "lm_head": 3, "final_norm": 4}
_ROW_BLOCKS = 8


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number below 2**64 (the two 32-bit halves,
    as ``PRNGKey`` packs a 64-bit seed)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def quantize(w: jax.Array, fmt: str, group: int):
    """float32 (K, N) → (payload int8, scales bf16 (K // group, N))."""
    K, N = w.shape
    qmax, qmin = (127.0, -127.0) if fmt == "q8_0" else (7.0, -8.0)
    wg = w.reshape(K // group, group, N)
    scale = jnp.max(jnp.abs(wg), axis=1) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(wg / scale[:, None, :]), qmin, qmax)
    q = q.astype(jnp.int32).reshape(K, N)
    if fmt == "q4_0":
        q = (q[0::2] & 0xF) | ((q[1::2] & 0xF) << 4)
    elif fmt != "q8_0":
        raise ValueError(f"unknown weight format {fmt!r}")
    return {"q": q.astype(jnp.int8), "s": scale.astype(jnp.bfloat16)}


def dequantize(leaf: Dict[str, jax.Array], fmt: str) -> jax.Array:
    """Inverse of :func:`quantize`, in float32: payload times scale."""
    q = leaf["q"].astype(jnp.int32)
    if fmt == "q4_0":
        lo = (q << 28) >> 28
        hi = (q << 24) >> 28
        q = jnp.stack([lo, hi], axis=-2)
        q = q.reshape(q.shape[:-3] + (2 * q.shape[-3], q.shape[-1]))
    s = leaf["s"].astype(jnp.float32)
    K, N = q.shape[-2:]
    G = s.shape[-2]
    qg = q.astype(jnp.float32).reshape(q.shape[:-2] + (G, K // G, N))
    return (qg * s[..., :, None, :]).reshape(q.shape[:-2] + (K, N))


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _layer(key, conf):
    m = work.dims(conf)
    s = conf["serving"]
    keys = jax.random.split(key, 6)
    out = {}
    for i, (name, K, N) in enumerate(work.layer_matmuls(conf)):
        out[name] = quantize(_normal(keys[i], (K, N), K ** -0.5),
                             s["weights"], s["group"])
    for i, name in ((4, "attn_norm"), (5, "ffn_norm")):
        out[name] = (1.0 + _normal(keys[i], (m.D,), 0.1)).astype(
            jnp.bfloat16)
    return out


def _make(key, conf):
    m = work.dims(conf)
    s = conf["serving"]
    k = {n: jax.random.fold_in(key, i) for n, i in _LEAF_IDS.items()}
    layer_keys = jax.vmap(lambda l: jax.random.fold_in(k["layers"], l))(
        jnp.arange(m.L))
    w = jax.lax.map(lambda lk: _layer(lk, conf), layer_keys)
    nb = _ROW_BLOCKS if m.V % (_ROW_BLOCKS * 256) == 0 else 1
    blk = jnp.arange(nb)

    def emb_rows(b):
        return _normal(jax.random.fold_in(k["embedding"], b),
                       (m.V // nb, m.D), m.D ** -0.5).astype(jnp.bfloat16)
    w["embedding"] = jax.lax.map(emb_rows, blk).reshape(m.V, m.D)

    def head_cols(b):
        return quantize(_normal(jax.random.fold_in(k["lm_head"], b),
                                (m.D, m.V // nb), m.D ** -0.5),
                        s["weights"], s["group"])
    head = jax.lax.map(head_cols, blk)
    w["lm_head"] = {n: jnp.moveaxis(a, 0, 1).reshape(a.shape[1], m.V)
                    for n, a in head.items()}
    w["final_norm"] = (1.0 + _normal(k["final_norm"], (m.D,), 0.1)
                       ).astype(jnp.bfloat16)
    return w


_MAKERS: Dict[str, object] = {}


def make(seed: int, conf: dict):
    """The whole model's weights from ``seed``, in one jitted call."""
    name = conf["name"]
    if name not in _MAKERS:
        _MAKERS[name] = jax.jit(functools.partial(_make, conf=conf))
    return _MAKERS[name](seed_key(seed))
