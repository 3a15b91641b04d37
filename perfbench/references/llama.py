"""Plain float32 reference of a llama-architecture decoder.

Pre-norm RMSNorm blocks; rotary position embeddings that rotate the
two halves of each head (LLaMA's ``rotate_half``); grouped-query causal
attention with softmax scale ``head_dim ** -0.5``; a SwiGLU MLP
(``silu(x W_gate) * (x W_up)``, then ``W_down``); a final RMSNorm and an
untied output head. This is DeepSeek LLM's and Mistral NeMo's published
architecture. Written from that description; it imports nothing of the
program under test.

Weights come from ``perfbench.weights`` (the benchmark's own seeded
maker) in their stored form and are dequantized here, one layer at a
time. Where the configuration stores the KV cache quantized, K and V
are rounded to that format per position, as the stored cache holds
them. Every matmul runs at ``highest`` precision, in float32.

With ``activations`` set (a dtype name), every matmul input is
rounded to that dtype: the same model one precision down, for the
control of the correctness check where the program has no such path
of its own.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights as wts
from perfbench import work

_QMAX = {"q8_0": (127.0, -127.0), "q4_0": (7.0, -8.0)}


def fake_quant(x: jax.Array, fmt: str, group: int, axis: int) -> jax.Array:
    """Round ``x`` to ``fmt`` in groups of ``group`` along ``axis`` and
    back: scale = group max / qmax, stored in bf16."""
    qmax, qmin = _QMAX[fmt]
    x = jnp.moveaxis(x, axis, -1)
    d = x.shape[-1]
    g = work.kv_group(d, group)
    xg = x.reshape(x.shape[:-1] + (d // g, g))
    scale = jnp.max(jnp.abs(xg), axis=-1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xg / scale), qmin, qmax)
    out = (q * scale.astype(jnp.bfloat16).astype(jnp.float32)).reshape(
        x.shape)
    return jnp.moveaxis(out, -1, axis)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (B, S, H, hd), pos (S,): rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class Reference:
    def __init__(self, conf: dict, activations: Optional[str] = None):
        self.conf = conf
        self.m = work.dims(conf)
        self.activations = activations
        self.eps = float(conf["rms_norm_eps"])
        s = conf["serving"]
        self.fmt, self.group = s["weights"], int(s["group"])
        self.kv = s["kv_cache"]
        self._layer = jax.jit(self._layer_impl)
        self._head = jax.jit(self._head_impl)
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))

    def _a(self, x):
        if self.activations:
            x = x.astype(jnp.dtype(self.activations)).astype(jnp.float32)
        return x

    def _mm(self, x, leaf):
        return jnp.dot(self._a(x), wts.dequantize(leaf, self.fmt))

    # -- one layer, one program for every layer ----------------------------
    def _layer_impl(self, x, layers, l):
        m = self.m
        p = jax.tree_util.tree_map(lambda a: a[l], layers)
        B, S, _ = x.shape
        pos = jnp.arange(S)
        h = _rms(x, p["attn_norm"], self.eps)
        qkv = self._mm(h, p["wqkv"])
        q = qkv[..., :m.qd].reshape(B, S, m.Hq, m.hd)
        k = qkv[..., m.qd:m.qd + m.kvd].reshape(B, S, m.Hkv, m.hd)
        v = qkv[..., m.qd + m.kvd:].reshape(B, S, m.Hkv, m.hd)
        theta = float(self.conf["rope_theta"])
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        if self.kv in ("q8_0", "q4_0"):
            k = fake_quant(k, self.kv, self.group, axis=-1)
            v = fake_quant(v, self.kv, self.group, axis=-1)
        G = m.Hq // m.Hkv
        qg = q.reshape(B, S, m.Hkv, G, m.hd)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", self._a(qg), self._a(k))
        s = s * m.hd ** -0.5
        causal = pos[None, :] <= pos[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", self._a(a), self._a(v))
        x = x + self._mm(o.reshape(B, S, m.qd), p["wo"])
        h = _rms(x, p["ffn_norm"], self.eps)
        gu = self._mm(h, p["w_gate_up"])
        y = jax.nn.silu(gu[..., :m.F]) * gu[..., m.F:]
        return x + self._mm(y, p["w_down"])

    def _head_impl(self, x, final_norm, lm_head, read_pos):
        """Logits at ``read_pos`` (B, T) of each row: (B, T, V)."""
        x = jnp.take_along_axis(x, read_pos[..., None], axis=1)
        x = _rms(x, final_norm, self.eps)
        return self._mm(x, lm_head)

    def logits(self, w, tokens: np.ndarray, read_pos: np.ndarray):
        """float32 logits of the rows ``tokens`` (B, S), teacher-forced,
        at positions ``read_pos`` (B, T); ``w`` from ``weights.make``."""
        layer_keys = ("wqkv", "wo", "w_gate_up", "w_down", "attn_norm",
                      "ffn_norm")
        layers = {k: w[k] for k in layer_keys}
        with jax.default_matmul_precision("highest"):
            x = self._embed(w["embedding"], jnp.asarray(tokens))
            for l in range(self.m.L):
                x = self._layer(x, layers, l)
            return self._head(x, w["final_norm"], w["lm_head"],
                              jnp.asarray(read_pos))


@functools.partial(jax.jit, static_argnames="kmax")
def served_gaps(ref, served, k, kmax: int):
    """How far each served token's logit (B, T) lies below the ``k``-th
    best logit of the reference at its position, 0 where it lies at or
    above it. ``k`` (B,) is each row's support: 1 for a greedy request,
    its top-k for a sampled one, 0 where the whole vocabulary may be
    drawn (no gap); ``kmax`` is the largest."""
    got = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    best = jax.lax.top_k(ref, kmax)[0]                      # (B, T, kmax)
    kth = jnp.take_along_axis(
        best, jnp.clip(k - 1, 0, kmax - 1)[:, None, None], axis=-1)[..., 0]
    kth = jnp.where((k > 0)[:, None], kth, -jnp.inf)
    return jnp.maximum(kth - got, 0.0)
