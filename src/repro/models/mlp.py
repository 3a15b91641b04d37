"""FFN block — the paper's dominant GEMM cost center (Fig 6).

A gated MLP runs its gate and up projections as one GEMM
(``w_gate_up``, gate then up); a plain MLP has ``w_up`` alone.
Column/row Megatron sharding comes from the logical axes: gate/up are
column-parallel on ``mlp``, down is row-parallel back to ``embed``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import layers
from repro.models.params import ParamSpec


def mlp_specs(cfg: ModelConfig) -> Dict:
    D, F = cfg.d_model, cfg.d_ff
    specs: Dict = {}
    if cfg.glu:
        specs["w_gate_up"] = layers.linear_spec(D, 2 * F, ("embed", "mlp"))
    else:
        specs["w_up"] = layers.linear_spec(D, F, ("embed", "mlp"))
    specs["w_down"] = layers.linear_spec(F, D, ("mlp", "embed"))
    return specs


def mlp_forward(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    act = layers.activation_fn(cfg.activation)
    up_kw = dict(use_pallas=cfg.use_pallas)
    if cfg.glu:
        gu = layers.linear(p["w_gate_up"], x, **up_kw)
        gu = constrain(gu, ("batch", None, "mlp"))
        g, u = jnp.split(gu, 2, axis=-1)
        h = act(g) * u
    else:
        h = act(layers.linear(p["w_up"], x, **up_kw))
    h = constrain(h, ("batch", None, "mlp"))
    return layers.linear(p["w_down"], h, **up_kw)
