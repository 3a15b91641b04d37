"""Shared building blocks: norms, RoPE, embeddings, (fused) projections.

Projections that read the same input are fused into one wide matmul
({Q,K,V}, {ffn_gate, ffn_up}, the SSD in_proj pieces): one MXU launch
and one weight stream instead of three strided ones.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models.params import ParamSpec


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, weight, bias, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) or (..., H, D) with positions (..., S) or (...,)."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                     # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]              # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    specs = {"embedding": ParamSpec((cfg.padded_vocab, cfg.d_model),
                                    ("vocab", "embed"), init="fan_out")}
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.padded_vocab),
                                     ("embed", "vocab"))
    return specs


def embed(params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["embedding"], tokens, axis=0)


def unembed(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            w = params["embedding"].T
        else:
            w = params["lm_head"]
        logits = ops.matmul(x, w, out_dtype=jnp.float32)
        return constrain(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# Linear / fused projections
# ---------------------------------------------------------------------------

def linear_spec(d_in: int, d_out: int, axes: Tuple[Optional[str], ...],
                bias: bool = False, bias_axis: Optional[str] = None):
    out = {"w": ParamSpec((d_in, d_out), axes)}
    if bias:
        out["b"] = ParamSpec((d_out,), (bias_axis,), init="zeros")
    return out


def linear(p, x: jax.Array, *, use_pallas: bool = False) -> jax.Array:
    y = ops.matmul(x, p["w"], use_pallas=use_pallas)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def gelu(x: jax.Array) -> jax.Array:
    """tanh-approximation GELU with the tanh written as 2σ(2z)−1.

    ``jax.nn.gelu`` lowers to a ``tanh`` HLO, which this container's
    XLA cannot partition under SPMD (``UNIMPLEMENTED: tanh`` on the
    multi-pod mesh). The logistic form is mathematically identical,
    numerically stable in both tails, and partitions fine (``silu``
    archs already compile through the same lowering).
    """
    xf = x.astype(jnp.float32)
    z = 0.7978845608028654 * (xf + 0.044715 * xf * xf * xf)
    t = 2.0 * jax.nn.sigmoid(2.0 * z) - 1.0
    return (0.5 * xf * (1.0 + t)).astype(x.dtype)


def activation_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": gelu,
            "relu": jax.nn.relu}[name]
