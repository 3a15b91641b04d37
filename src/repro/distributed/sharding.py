"""Logical-axis sharding rules (GSPMD / pjit).

Models annotate every parameter and key activation with *logical* axis
names; the rules below map those to physical mesh axes. The four rule
sets encode the paper's §7 execution versions on a pod (DESIGN.md §2):

- ``v0``/``v1`` — no tensor parallelism (the paper's CPU-threads-only
  configurations). Weights are FSDP-sharded on ``data`` only so that
  compile-time memory still fits; the ``model`` axis carries sequence
  sharding only. The two share one rule set.
- ``v2`` — fusion + tensor parallelism: Megatron column/row sharding on
  ``model``, FSDP on ``data``, batch on (``pod``, ``data``). The
  production default.
- ``v3`` — the paper's regression case: the attention block and the FFN
  block are deliberately sharded on *different* mesh axes, so GSPMD must
  reshard the residual stream at every block boundary. This reproduces,
  structurally, the CPU+GPU split that dropped throughput from 15 to
  6 tk/s: the collective term explodes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis name → physical mesh axes (or None)."""
    name: str
    rules: Dict[str, MeshAxes]

    def get(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        return self.rules.get(logical, None)


# Logical axes used throughout the code base:
#   batch      — global batch
#   seq        — sequence (activations; sharded for sequence parallelism)
#   kv_seq     — KV-cache sequence dim
#   embed      — d_model / residual feature dim of *parameters* (FSDP dim)
#   mlp        — d_ff (column-parallel dim)
#   heads      — attention projection output features (q_dim / kv_dim)
#   qkv_fused  — fused QKV output features
#   vocab      — vocabulary dim
#   expert     — MoE expert dim
#   conv       — ssm conv kernel dim
#   state      — ssm state dim
#   act_embed  — d_model of *activations* (normally unsharded)

_COMMON = {
    "batch": ("pod", "data"),
    "act_embed": None,
    "conv": None,
    "state": None,
    "expert_cap": ("pod", "data"),   # MoE token-buffer capacity dim
}

RULES_V0 = AxisRules("v0", {
    **_COMMON,
    "seq": "model",      # seq-shard activations so full models fit
    "kv_seq": "model",
    "embed": "data",     # FSDP only — no tensor parallelism (paper v0/v1)
    "mlp": None,
    "heads": None,
    "qkv_fused": None,
    "vocab": None,
    "expert": "model",   # experts are data-independent; always shardable
})

RULES_V1 = AxisRules("v1", dict(RULES_V0.rules))

RULES_V2 = AxisRules("v2", {
    **_COMMON,
    "seq": "model",
    "kv_seq": "model",
    "embed": "data",     # FSDP
    "mlp": "model",      # Megatron column-parallel
    "heads": "model",
    "qkv_fused": "model",
    "vocab": "model",
    "expert": "model",
})

# v3: FFN tensor-sharded on *data*, attention on *model* — the
# cross-device split. Batch for FFN lands on model: every block boundary
# re-lays-out the residual stream.
RULES_V3 = AxisRules("v3", {
    **_COMMON,
    "seq": None,
    "kv_seq": "model",
    "embed": None,
    "mlp": "data",       # <-- conflicting axis: forces reshard per block
    "heads": "model",
    "qkv_fused": "model",
    "vocab": "model",
    "expert": "data",
    "expert_cap": "model",
})

# Beyond-paper ruleset (§Perf): full 2-D tensor parallelism for decode.
# v2's FSDP dimension ("embed" → data) forces an all-gather of every
# layer's weights each decode step — fine for training (amortized over
# 1M tokens), catastrophic for decode (128 tokens/step). tp2d shards
# every weight over BOTH mesh axes on its *output* features so each
# chip streams only params/256 bytes per step and the only collectives
# are small activation all-reduces after row-parallel projections.
RULES_TP2D = AxisRules("tp2d", {
    "batch": "data",          # KV cache batch dim
    "act_embed": None,
    "conv": None,
    "state": None,
    "expert_cap": None,
    "seq": None,
    "kv_seq": "model",
    "embed": None,            # no FSDP dim — weights fully TP-sharded
    "mlp": ("data", "model"),
    "heads": ("data", "model"),
    "qkv_fused": ("data", "model"),
    "vocab": ("data", "model"),
    "expert": ("data", "model"),
})

# Iteration 2 for decode (tp2d was refuted — see
# EXPERIMENTS.md §Perf): classic 1-D Megatron TP on `model` only, no
# FSDP dim. Weights replicate across `data`; affordable only when
# quantized (q4_0: 110B x 0.5625B / 16 = 3.9 GB/chip), which is exactly
# the paper's Q4 lever applied at pod scale. Batch/KV stay on `data`,
# so the only collective is the per-layer row-parallel all-reduce.
RULES_TP1D = AxisRules("tp1d", {
    "batch": ("pod", "data"),
    "act_embed": None,
    "conv": None,
    "state": None,
    "expert_cap": ("pod", "data"),
    "seq": None,
    "kv_seq": "model",
    "embed": None,            # replicated input dim (no FSDP gathers)
    "mlp": "model",
    "heads": "model",
    "qkv_fused": "model",
    "vocab": "model",
    "expert": "model",
})

# v2 with experts sharded over BOTH mesh axes (kimi-k2 has 384 experts
# = 1.5/chip at 256 chips; the token buffer then reshards once
# data->expert instead of scatter across model while batch-sharded on
# data).
RULES_V2E = AxisRules("v2e", {
    **RULES_V2.rules,
    "expert": ("data", "model"),
    "expert_cap": None,
})

# v2 without sequence parallelism (activations replicated on
# seq). Tests whether the train-shape collective term is dominated by
# the seq@model <-> heads@model residual resharding per block.
RULES_V2NS = AxisRules("v2ns", {**RULES_V2.rules, "seq": None})

# v2e + no sequence parallelism (kimi iteration 3)
RULES_V2ENS = AxisRules("v2ens", {**RULES_V2E.rules, "seq": None})

_RULESETS = {"v0": RULES_V0, "v1": RULES_V1, "v2": RULES_V2,
             "v3": RULES_V3, "tp2d": RULES_TP2D, "tp1d": RULES_TP1D,
             "v2e": RULES_V2E, "v2ns": RULES_V2NS, "v2ens": RULES_V2ENS}


def rules_for(version: str) -> AxisRules:
    return _RULESETS[version]


def _filter_axes(axes: MeshAxes, mesh: Optional[Mesh]) -> MeshAxes:
    """Drop mesh axes that don't exist (e.g. no 'pod' on single-pod)."""
    if axes is None:
        return None
    names = mesh.axis_names if mesh is not None else ("pod", "data", "model")
    if isinstance(axes, str):
        return axes if axes in names else None
    kept = tuple(a for a in axes if a in names)
    return kept if kept else None


def logical_to_spec(logical: Sequence[Optional[str]], rules: AxisRules,
                    mesh: Optional[Mesh] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    used = set()
    out = []
    for ax in logical:
        phys = _filter_axes(rules.get(ax), mesh)
        # A mesh axis may appear at most once in a spec; later wins → None
        if phys is not None:
            flat = (phys,) if isinstance(phys, str) else phys
            if any(f in used for f in flat):
                phys = None
            else:
                used.update(flat)
        out.append(phys)
    # trim trailing Nones for tidiness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x, logical: Sequence[Optional[str]],
              rules: Optional[AxisRules] = None,
              mesh: Optional[Mesh] = None):
    """with_sharding_constraint by logical axes (no-op off-mesh).

    Mesh and rules default to the ``repro.distributed.context`` values,
    so model code stays mesh-agnostic and runs unmodified on one device.
    """
    from repro.distributed import context as ctx
    env_mesh = mesh if mesh is not None else ctx.current_mesh()
    if env_mesh is None:
        return x
    if rules is None:
        rules = ctx.current_rules() or RULES_V2
    spec = logical_to_spec(logical, rules, env_mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(env_mesh, spec))


def named_sharding(mesh: Mesh, logical: Sequence[Optional[str]],
                   rules: AxisRules) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical, rules, mesh))


def sanitize_spec(shape: Sequence[int], spec: P, mesh: Mesh) -> P:
    """Drop spec entries whose mesh extent doesn't divide the dim.

    jit in_shardings require exact divisibility; odd vocabularies
    (50280, 256206) or batch=1 long-context shapes fall back to
    replication on that dim (GSPMD re-shards internally as needed).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        extent = 1
        for a in axes:
            extent *= sizes[a]
        out.append(entry if shape[i] % extent == 0 else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_shardings(abs_tree, pspec_tree, mesh: Mesh):
    """NamedShardings for a ShapeDtypeStruct tree, sanitized per leaf."""
    from repro.quant.quantize import QuantizedTensor

    def mk(leaf, spec):
        return NamedSharding(mesh, sanitize_spec(leaf.shape, spec, mesh))

    return jax.tree_util.tree_map(
        mk, abs_tree, pspec_tree,
        is_leaf=lambda x: (not isinstance(x, QuantizedTensor)
                           and hasattr(x, "shape") and hasattr(x, "dtype")))
