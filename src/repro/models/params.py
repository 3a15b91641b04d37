"""Parameter-spec system: shape + logical axes + init, defined once.

Every model family builds a nested dict of :class:`ParamSpec`; from it
we derive (a) initialized arrays, (b) PartitionSpecs for pjit, and
(c) ShapeDtypeStructs for the dry-run — guaranteed consistent because
they come from the same source of truth.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import AxisRules, logical_to_spec
from repro.quant.quantize import QuantizedTensor


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == ndim
    init: str = "normal"              # normal | zeros | ones | small_a
    scale: float = 1.0                # stddev multiplier for normal

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec_tree_leaf(x) -> bool:
    return isinstance(x, ParamSpec)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _scaled_normal(key, shape, std, dtype):
    """One leaf's ``normal * std`` cast, fused by XLA: on a TPU no f32
    copy of the leaf is materialized (a stacked gate/up leaf of a 7B
    model would take 10.8 GB in f32). The no-op ``reduce_precision``
    keeps XLA from folding ``std`` into the sampler's own ``* sqrt(2)``,
    which would round differently from the eager ops and change bits."""
    z = jax.lax.reduce_precision(
        jax.random.normal(key, shape, jnp.float32),
        exponent_bits=8, mantissa_bits=23)
    return (z * std).astype(dtype)


def init_params(specs, rng: jax.Array, dtype=jnp.bfloat16):
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=is_spec_tree_leaf)
    keys = jax.random.split(rng, len(leaves))

    def mk(spec: ParamSpec, key):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        if spec.init == "small_a":
            # RG-LRU recurrence parameter: a = sigmoid(x)^(1/c) near 1;
            # init the underlying logit in a stable range
            return jnp.full(spec.shape, 4.0, dtype)
        if spec.init == "fan_out":
            # embeddings: std 1/sqrt(d_model) so tied unembedding gives
            # O(1) logits
            std = spec.scale * (spec.shape[-1] ** -0.5)
            return _scaled_normal(key, spec.shape, jnp.float32(std), dtype)
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = spec.scale * (fan_in ** -0.5)
        return _scaled_normal(key, spec.shape, jnp.float32(std), dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [mk(s, k) for s, k in zip(leaves, keys)])


def abstract_params(specs, dtype=jnp.bfloat16):
    """ShapeDtypeStructs — dry-run stand-ins, no allocation."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs, is_leaf=is_spec_tree_leaf)


def param_pspecs(specs, rules: AxisRules, mesh: Optional[Mesh] = None):
    """PartitionSpec tree parallel to the spec tree."""
    return jax.tree_util.tree_map(
        lambda s: logical_to_spec(s.axes, rules, mesh),
        specs, is_leaf=is_spec_tree_leaf)


def param_shardings(specs, rules: AxisRules, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, logical_to_spec(s.axes, rules, mesh)),
        specs, is_leaf=is_spec_tree_leaf)


def match_quantized(tree, params):
    """Expand a per-param tree (specs/shardings) to match a param pytree
    that contains QuantizedTensor nodes.

    For a QuantizedTensor leaf, data and scales reuse the weight's
    entry: their layouts preserve the (K, N) axis order (K possibly
    packed/grouped, which only changes sizes, not axis meaning).
    """
    def walk(entry, p):
        if isinstance(p, QuantizedTensor):
            return QuantizedTensor(data=entry, scales=entry, fmt=p.fmt,
                                   group=p.group)
        if isinstance(p, dict):
            return {k: walk(entry[k], v) for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            return type(p)(walk(e, v) for e, v in zip(entry, p))
        return entry

    return walk(tree, params)


def count_params(params) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.size
    return total
