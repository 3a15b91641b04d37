"""Unified model: every assigned family behind one API.

``Model`` exposes:
- ``param_specs() / init(rng) / abstract_params()`` — one source of truth
  for shapes, logical sharding axes and dry-run stand-ins.
- ``forward(params, batch)`` — full-sequence logits (training).
- ``loss(params, batch)`` — next-token CE (+ MoE aux loss).
- ``prefill(params, batch, cache)`` — process the prompt, fill caches,
  return last-position logits.
- ``decode_step(params, tokens, cache)`` — one token for the whole
  batch (the paper's decode phase; what ``decode_32k``/``long_500k``
  dry-runs lower).
- ``init_cache(batch, seq_len) / cache_axes()`` — per-family cache
  pytrees (KV ring buffers, SSD states, RG-LRU states, cross-attn KV).

Homogeneous stacks (dense/moe/ssm/vlm/audio) scan over stacked layer
params; the hybrid 1:2 pattern uses a python loop (26 layers).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import hybrid as hy
from repro.models import layers
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.params import (
    ParamSpec, abstract_params, init_params, is_spec_tree_leaf)
from repro.quant.quantize import quantize_tree


# ---------------------------------------------------------------------------
# Spec helpers
# ---------------------------------------------------------------------------

def stack_specs(n: int, specs):
    """Add a leading layer dim to every spec (for scan-over-layers)."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n,) + s.shape, (None,) + s.axes, s.init,
                            s.scale),
        specs, is_leaf=is_spec_tree_leaf)


def _norm_spec(d):
    return ParamSpec((d,), (None,), init="ones")


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- parameter tree ---------------------------------------------------
    def _attn_layer_specs(self, cross: bool = False) -> Dict:
        cfg = self.cfg
        s = {
            "attn_norm": _norm_spec(cfg.d_model),
            "attn": attn.attention_specs(cfg),
            "ffn_norm": _norm_spec(cfg.d_model),
        }
        if cross:
            s["cross_norm"] = _norm_spec(cfg.d_model)
            s["cross"] = attn.attention_specs(cfg, cross=True)
        if cfg.is_moe:
            s["moe"] = moe_mod.moe_specs(cfg)
        else:
            s["mlp"] = mlp_mod.mlp_specs(cfg)
        return s

    def _ssm_layer_specs(self) -> Dict:
        return {"norm": _norm_spec(self.cfg.d_model),
                "ssm": ssm_mod.ssm_specs(self.cfg)}

    def _hybrid_layer_specs(self, kind: str) -> Dict:
        cfg = self.cfg
        if kind == "rglru":
            temporal = {"rglru": hy.rglru_specs(cfg)}
        else:
            temporal = {"attn": attn.attention_specs(cfg)}
        return {"attn_norm": _norm_spec(cfg.d_model), **temporal,
                "ffn_norm": _norm_spec(cfg.d_model),
                "mlp": mlp_mod.mlp_specs(cfg)}

    def param_specs(self) -> Dict:
        cfg = self.cfg
        specs: Dict[str, Any] = layers.embed_specs(cfg)
        specs["final_norm"] = _norm_spec(cfg.d_model)
        if cfg.arch_type in ("dense", "moe", "vlm"):
            specs["layers"] = stack_specs(cfg.num_layers,
                                          self._attn_layer_specs())
        elif cfg.arch_type == "ssm":
            specs["layers"] = stack_specs(cfg.num_layers,
                                          self._ssm_layer_specs())
        elif cfg.arch_type == "hybrid":
            specs["layers"] = [self._hybrid_layer_specs(k)
                               for k in cfg.layer_pattern()]
        elif cfg.arch_type == "audio":
            specs["encoder"] = stack_specs(
                cfg.num_encoder_layers, self._enc_layer_specs())
            specs["enc_norm"] = _norm_spec(cfg.d_model)
            specs["layers"] = stack_specs(
                cfg.num_layers, self._attn_layer_specs(cross=True))
        else:
            raise ValueError(cfg.arch_type)
        return specs

    def _enc_layer_specs(self) -> Dict:
        cfg = self.cfg
        return {"attn_norm": _norm_spec(cfg.d_model),
                "attn": attn.attention_specs(cfg),
                "ffn_norm": _norm_spec(cfg.d_model),
                "mlp": mlp_mod.mlp_specs(cfg)}

    def init(self, rng: jax.Array, quantize: Optional[bool] = None):
        cfg = self.cfg
        dtype = jnp.bfloat16 if cfg.param_dtype == "bf16" else jnp.float32
        params = init_params(self.param_specs(), rng, dtype)
        do_quant = (cfg.quant_policy not in ("bf16", "f16", "f32")
                    if quantize is None else quantize)
        if do_quant:
            params = quantize_tree(params, cfg.quant_policy,
                                   cfg.quant_group)
        return params

    def abstract_params(self):
        cfg = self.cfg
        dtype = jnp.bfloat16 if cfg.param_dtype == "bf16" else jnp.float32
        abs_tree = abstract_params(self.param_specs(), dtype)
        if cfg.quant_policy not in ("bf16", "f16", "f32"):
            # quantized stand-ins so the dry-run sees int4/int8 storage
            abs_tree = jax.eval_shape(
                lambda p: quantize_tree(p, cfg.quant_policy,
                                        cfg.quant_group), abs_tree)
        return abs_tree

    # -- windowing policy ---------------------------------------------------
    def window_for(self, total_len: int, kind: str = "attn") -> int:
        cfg = self.cfg
        if cfg.arch_type == "hybrid":
            return cfg.local_attn_window
        if cfg.sliding_window:
            return cfg.sliding_window
        if total_len > cfg.max_full_attn:
            return cfg.window_long_ctx   # long-context fallback (DESIGN §4)
        return 0

    # -- blocks --------------------------------------------------------------
    def _attn_block(self, p, x, positions, window, enc_out=None):
        cfg = self.cfg
        h = layers.rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        h = attn.attention_forward(p["attn"], cfg, h, positions=positions,
                                   window=window)
        x = x + h
        if enc_out is not None and "cross" in p:
            h = layers.rmsnorm(x, p["cross_norm"], cfg.norm_eps)
            k, v = self._cross_kv(p["cross"], enc_out)
            h = attn.attention_forward(p["cross"], cfg, h,
                                       positions=positions,
                                       kv_override=(k, v), use_rope=False)
            x = x + h
        h = layers.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
        aux = 0.0
        if cfg.is_moe:
            h, aux = moe_mod.moe_forward(p["moe"], cfg, h)
        else:
            h = mlp_mod.mlp_forward(p["mlp"], cfg, h)
        x = x + h
        x = constrain(x, ("batch", "seq", "act_embed"))
        return x, aux

    def _cross_kv(self, p, enc_out):
        cfg = self.cfg
        B, S_enc, _ = enc_out.shape
        k = layers.linear(p["wk"], enc_out).reshape(
            B, S_enc, cfg.num_kv_heads, cfg.head_dim).swapaxes(1, 2)
        v = layers.linear(p["wv"], enc_out).reshape(
            B, S_enc, cfg.num_kv_heads, cfg.head_dim).swapaxes(1, 2)
        return k, v

    def _run_stack(self, params_layers, x, positions, window,
                   enc_out=None):
        cfg = self.cfg

        def body(carry, p_l):
            h, aux = carry
            h, a = self._attn_block(p_l, h, positions, window, enc_out)
            return (h, aux + a), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = _layer_scan(body_fn, (x, 0.0), params_layers,
                                  cfg.unroll_scans)
        return x, aux

    # -- encoder (audio) -------------------------------------------------------
    def _encode(self, params, frames):
        cfg = self.cfg
        x = frames.astype(jnp.bfloat16 if cfg.dtype == "bf16"
                          else jnp.float32)
        x = constrain(x, ("batch", "seq", "act_embed"))
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                     x.shape[:2])

        def body(carry, p_l):
            h = carry
            z = layers.rmsnorm(h, p_l["attn_norm"], cfg.norm_eps)
            # bidirectional self-attention
            z = attn.attention_forward(p_l["attn"], cfg, z,
                                       positions=positions,
                                       kv_override=None, use_rope=True)
            h = h + z
            z = layers.rmsnorm(h, p_l["ffn_norm"], cfg.norm_eps)
            h = h + mlp_mod.mlp_forward(p_l["mlp"], cfg, z)
            return h, None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = _layer_scan(body_fn, x, params["encoder"],
                           cfg.unroll_scans)
        return layers.rmsnorm(x, params["enc_norm"], cfg.norm_eps)

    # -- full-sequence forward (training / prefill core) -----------------------
    def forward(self, params, batch: Dict,
                return_hidden: bool = False):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = layers.embed(params, tokens)
        prefix_len = 0
        if cfg.arch_type == "vlm":
            x, prefix_len = _prepend_prefix(batch["prefix"], x)
        x = constrain(x, ("batch", "seq", "act_embed"))
        total = S + prefix_len
        positions = jnp.broadcast_to(jnp.arange(total), (B, total))
        window = self.window_for(total)
        enc_out = None
        if cfg.arch_type == "audio":
            enc_out = self._encode(params, batch["frames"])

        aux = 0.0
        if cfg.arch_type == "hybrid":
            for p_l, kind in zip(params["layers"], cfg.layer_pattern()):
                x = self._hybrid_block(p_l, kind, x, positions)
        elif cfg.arch_type == "ssm":
            def body(carry, p_l):
                h = carry
                z = layers.rmsnorm(h, p_l["norm"], cfg.norm_eps)
                h = h + ssm_mod.ssm_forward(p_l["ssm"], cfg, z)
                h = constrain(h, ("batch", "seq", "act_embed"))
                return h, None
            body_fn = jax.checkpoint(body) if cfg.remat else body
            x, _ = _layer_scan(body_fn, x, params["layers"],
                               cfg.unroll_scans)
        else:
            x, aux = self._run_stack(params["layers"], x, positions,
                                     window, enc_out)
        x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if prefix_len:
            x = x[:, prefix_len:]
        if return_hidden:
            return x, aux
        logits = layers.unembed(params, x, cfg)
        return logits, aux

    def _hybrid_block(self, p_l, kind, x, positions):
        cfg = self.cfg
        h = layers.rmsnorm(x, p_l["attn_norm"], cfg.norm_eps)
        if kind == "rglru":
            h = hy.rglru_forward(p_l["rglru"], cfg, h)
        else:
            h = attn.attention_forward(p_l["attn"], cfg, h,
                                       positions=positions,
                                       window=cfg.local_attn_window)
        x = x + h
        h = layers.rmsnorm(x, p_l["ffn_norm"], cfg.norm_eps)
        x = x + mlp_mod.mlp_forward(p_l["mlp"], cfg, h)
        return constrain(x, ("batch", "seq", "act_embed"))

    # -- loss -------------------------------------------------------------------
    def loss(self, params, batch: Dict):
        logits, aux = self.forward(params, batch)
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = jnp.ones_like(ll).at[:, -1].set(0.0)
        ce = -jnp.sum(ll * mask) / jnp.sum(mask)
        return ce + aux

    # =========================================================================
    # Serving: prefill + decode
    # =========================================================================

    def kv_quant_effective(self, kv_quant: Optional[str] = None) -> str:
        """Cache precision actually served. Recurrent families (SSM /
        RG-LRU hybrid) keep bf16 state regardless of ``cfg.kv_quant``:
        their per-layer state is small (no growth with context) and the
        sequential scan compounds rounding — ``kv_quant`` is a
        contract no-op there, verified by test."""
        kvq = self.cfg.kv_quant if kv_quant is None else kv_quant
        if self.cfg.arch_type in ("ssm", "hybrid"):
            return "bf16"
        return kvq

    def paging_effective(self, max_len: int, page_size: int) -> int:
        """Page size actually served, or 0 when the cache stays dense.

        Paging virtualizes *growing* full-attention caches; recurrent
        state (SSM / RG-LRU) is O(1) per slot and sliding-window rings
        are already capped, so a paged engine on those families is
        structurally dense — a contract no-op like ``kv_quant`` on
        recurrent archs."""
        if not page_size:
            return 0
        if self.cfg.arch_type not in ("dense", "moe", "vlm", "audio"):
            return 0
        if self.window_for(max_len):
            return 0
        return page_size

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   kv_quant: Optional[str] = None, page_size: int = 0,
                   cache_blocks: int = 0):
        cfg = self.cfg
        window = self.window_for(max_len)
        kvq = self.kv_quant_effective(kv_quant)
        page_size = self.paging_effective(max_len, page_size)
        if cfg.arch_type == "ssm":
            one = lambda: ssm_mod.init_ssm_cache(cfg, batch, dtype)
            cache = {"layers": _stack_pytrees(
                [one() for _ in range(cfg.num_layers)])}
        elif cfg.arch_type == "hybrid":
            per_layer = []
            for kind in cfg.layer_pattern():
                if kind == "rglru":
                    per_layer.append(hy.init_rglru_cache(cfg, batch, dtype))
                else:
                    per_layer.append(attn.init_kv_cache(
                        cfg, batch, max_len, cfg.local_attn_window, dtype))
            cache = {"layers": per_layer}
        else:
            one = lambda: attn.init_kv_cache(cfg, batch, max_len, window,
                                             dtype, kv_quant=kvq,
                                             page_size=page_size,
                                             num_blocks=cache_blocks)
            cache = {"layers": _stack_pytrees(
                [one() for _ in range(cfg.num_layers)])}
            if cfg.arch_type == "audio":
                L = cfg.num_layers
                S_enc = cfg.encoder_seq_len
                cache["cross_k"] = jnp.zeros(
                    (L, batch, cfg.num_kv_heads, S_enc, cfg.head_dim),
                    dtype)
                cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
                cache["cross_lens"] = jnp.zeros((batch,), jnp.int32)
        return cache

    def cache_axes(self, kv_quant: Optional[str] = None,
                   page_size: int = 0):
        """Logical axis names per cache leaf. Pass the *effective*
        ``page_size`` (see ``paging_effective``) to describe a paged
        cache — pool leaves then carry "kv_block"/"kv_page" instead of
        "batch"/"kv_seq", which is what keeps the batch-keyed
        splice/merge machinery off them."""
        cfg = self.cfg
        kvq = self.kv_quant_effective(kv_quant)
        if cfg.arch_type == "ssm":
            per = ssm_mod.ssm_cache_axes()
            return {"layers": jax.tree_util.tree_map(
                lambda a: (None,) + a, per,
                is_leaf=lambda x: isinstance(x, tuple))}
        if cfg.arch_type == "hybrid":
            out = []
            for kind in cfg.layer_pattern():
                out.append(hy.rglru_cache_axes() if kind == "rglru"
                           else attn.kv_cache_axes())
            return {"layers": out}
        axes = {"layers": jax.tree_util.tree_map(
            lambda a: (None,) + a,
            attn.kv_cache_axes(kvq, paged=bool(page_size)),
            is_leaf=lambda x: isinstance(x, tuple))}
        if cfg.arch_type == "audio":
            axes["cross_k"] = (None, "batch", None, "kv_seq", None)
            axes["cross_v"] = (None, "batch", None, "kv_seq", None)
            axes["cross_lens"] = ("batch",)
        return axes

    # -- prefill -----------------------------------------------------------------
    def prefill(self, params, batch: Dict, cache):
        """Run the prompt, fill the cache, return last-token logits.

        Optional ``batch["seq_lens"]`` (B,) marks per-sequence true
        lengths: tokens beyond ``seq_lens[i]`` are right-padding, so the
        engine can prefill several length-bucketed prompts in ONE
        dispatch. Padding rows write junk K/V past ``lens`` — harmless,
        because decode reads only ``kv_len = lens+1`` rows and
        overwrites the junk in order before it ever becomes visible.
        Recurrent archs (ssm/hybrid) carry state through every position,
        so callers must not pad them (the engine buckets those by exact
        length, making ``seq_lens`` uniform).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        seq_lens = batch.get("seq_lens")
        B, S = tokens.shape
        x = layers.embed(params, tokens)
        prefix_len = 0
        if cfg.arch_type == "vlm":
            x, prefix_len = _prepend_prefix(batch["prefix"], x)
        x = constrain(x, ("batch", "seq", "act_embed"))
        total = S + prefix_len
        positions = jnp.broadcast_to(jnp.arange(total), (B, total))
        window = self.window_for(total)

        new_cache = dict(cache)
        # effective per-sequence cache length incl. any VLM prefix
        eff_lens = None if seq_lens is None else seq_lens + prefix_len
        enc_out = None
        if cfg.arch_type == "audio":
            enc_out = self._encode(params, batch["frames"])

        if cfg.arch_type == "ssm":
            def body(carry, xs):
                h = carry
                p_l, c_l = xs
                z = layers.rmsnorm(h, p_l["norm"], cfg.norm_eps)
                z, (conv, state) = ssm_mod.ssm_forward(
                    p_l["ssm"], cfg, z, return_state=True)
                h = h + z
                c_new = dict(c_l, conv=conv,
                             state=state.astype(c_l["state"].dtype),
                             lens=c_l["lens"] + total)
                return h, c_new
            x, stacked = _layer_scan(body, x,
                                     (params["layers"], cache["layers"]),
                                     cfg.unroll_scans)
            new_cache["layers"] = stacked
        elif cfg.arch_type == "hybrid":
            new_layers = []
            for p_l, c_l, kind in zip(params["layers"], cache["layers"],
                                      cfg.layer_pattern()):
                x, c_new = self._hybrid_prefill_block(p_l, kind, x,
                                                      positions, c_l)
                new_layers.append(c_new)
            new_cache["layers"] = new_layers
        else:
            if cfg.arch_type == "audio":
                # precompute cross-attn KV once per layer
                def cross_body(_, p_l):
                    k, v = self._cross_kv(p_l["cross"], enc_out)
                    return None, (k, v)
                _, (ck, cv) = _layer_scan(cross_body, None,
                                          params["layers"],
                                          cfg.unroll_scans)
                new_cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
                new_cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
                new_cache["cross_lens"] = jnp.full(
                    (B,), enc_out.shape[1], jnp.int32)

            def body(carry, xs):
                h = carry
                p_l, c_l = xs
                z = layers.rmsnorm(h, p_l["attn_norm"], cfg.norm_eps)
                z, k, v = attn.attention_forward(
                    p_l["attn"], cfg, z, positions=positions,
                    window=window, return_kv=True)
                h = h + z
                c_new = _write_prefill_kv(c_l, k, v, total,
                                          seq_lens=eff_lens,
                                          kv_quant=cfg.kv_quant,
                                          group=cfg.quant_group)
                if "cross" in p_l:
                    z = layers.rmsnorm(h, p_l["cross_norm"], cfg.norm_eps)
                    kc, vc = self._cross_kv(p_l["cross"], enc_out)
                    z = attn.attention_forward(
                        p_l["cross"], cfg, z, positions=positions,
                        kv_override=(kc, vc), use_rope=False)
                    h = h + z
                z = layers.rmsnorm(h, p_l["ffn_norm"], cfg.norm_eps)
                if cfg.is_moe:
                    z, _ = moe_mod.moe_forward(p_l["moe"], cfg, z)
                else:
                    z = mlp_mod.mlp_forward(p_l["mlp"], cfg, z)
                h = h + z
                h = constrain(h, ("batch", "seq", "act_embed"))
                return h, c_new

            x, stacked = _layer_scan(body, x,
                                     (params["layers"], cache["layers"]),
                                     cfg.unroll_scans)
            new_cache["layers"] = stacked

        x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if eff_lens is None:
            last = x[:, -1:]
        else:
            # per-sequence last real position (right-padded batch)
            last = jnp.take_along_axis(
                x, (eff_lens - 1)[:, None, None], axis=1)
        logits = layers.unembed(params, last, cfg)[:, 0]
        return logits[:, :cfg.vocab_size], new_cache

    def _hybrid_prefill_block(self, p_l, kind, x, positions, c_l):
        cfg = self.cfg
        h = layers.rmsnorm(x, p_l["attn_norm"], cfg.norm_eps)
        if kind == "rglru":
            h, c_new = hy.rglru_forward(p_l["rglru"], cfg, h, cache=None,
                                        return_state=True)
            c_new["conv"] = c_new["conv"].astype(c_l["conv"].dtype)
        else:
            h, k, v = attn.attention_forward(
                p_l["attn"], cfg, h, positions=positions,
                window=cfg.local_attn_window, return_kv=True)
            c_new = _write_prefill_kv(c_l, k, v, x.shape[1])
        x = x + h
        h = layers.rmsnorm(x, p_l["ffn_norm"], cfg.norm_eps)
        x = x + mlp_mod.mlp_forward(p_l["mlp"], cfg, h)
        return constrain(x, ("batch", "seq", "act_embed")), c_new

    # -- decode ------------------------------------------------------------------
    def decode_step(self, params, tokens, cache, advance_mask=None):
        """tokens: (B, 1) → (logits (B, vocab), new_cache).

        ``advance_mask`` (B,) bool — rows where it is False keep their
        cache frozen (no K/V write, no ``lens`` advance, no state
        update). The serving megastep uses this so retired (EOS /
        length-capped) slots can keep riding the fixed-shape batch
        through a ``lax.scan`` without corrupting their cache — and,
        since every cache family writes at its own per-row ``lens``
        cursor, the same machinery carries the engine's *chunked
        prefill admission*: a prefilling slot feeds prompt tokens
        through this step one per scan substep (its logits discarded
        until the last prompt position) while its decoding neighbours
        advance normally. For attention caches this is bit-identical
        to ``prefill`` on this container's backend; recurrent archs
        differ only by sequential-vs-associative scan rounding.
        """
        cfg = self.cfg
        B = tokens.shape[0]
        x = layers.embed(params, tokens)
        x = constrain(x, ("batch", None, "act_embed"))

        new_cache = dict(cache)
        if cfg.arch_type == "ssm":
            def body(carry, xs):
                h = carry
                p_l, c_l = xs
                z = layers.rmsnorm(h, p_l["norm"], cfg.norm_eps)
                z, c_new = ssm_mod.ssm_decode(p_l["ssm"], cfg, z, c_l)
                return h + z, _freeze_rows(c_new, c_l, advance_mask)
            x, stacked = _layer_scan(body, x,
                                     (params["layers"], cache["layers"]),
                                     cfg.unroll_scans)
            new_cache["layers"] = stacked
        elif cfg.arch_type == "hybrid":
            new_layers = []
            for p_l, c_l, kind in zip(params["layers"], cache["layers"],
                                      cfg.layer_pattern()):
                h = layers.rmsnorm(x, p_l["attn_norm"], cfg.norm_eps)
                if kind == "rglru":
                    h, c_new = hy.rglru_decode(p_l["rglru"], cfg, h, c_l)
                else:
                    h, c_new = attn.attention_decode(p_l["attn"], cfg, h,
                                                     c_l)
                x = x + h
                h = layers.rmsnorm(x, p_l["ffn_norm"], cfg.norm_eps)
                x = x + mlp_mod.mlp_forward(p_l["mlp"], cfg, h)
                new_layers.append(_freeze_rows(c_new, c_l, advance_mask))
            new_cache["layers"] = new_layers
        else:
            # The stacked cache rides the layer scan's carry: each layer
            # writes its new rows in place and the attention kernels
            # read the stacked buffer by layer index, so the cache stays
            # one buffer through the step (scanning it as xs/ys would
            # rebuild the whole stacked cache on every step).
            cross = cfg.arch_type == "audio"

            def body(carry, xs):
                h, c = carry
                if cross:
                    p_l, l, ck, cv = xs
                else:
                    p_l, l = xs
                z = layers.rmsnorm(h, p_l["attn_norm"], cfg.norm_eps)
                z, c = attn.attention_decode(p_l["attn"], cfg, z, c,
                                             layer=l,
                                             write_mask=advance_mask)
                h = h + z
                if cross:
                    z = layers.rmsnorm(h, p_l["cross_norm"], cfg.norm_eps)
                    zc, _ = attn.attention_decode(
                        p_l["cross"], cfg, z,
                        {"cross_lens": cache["cross_lens"]},
                        kv_override=(ck, cv))
                    h = h + zc
                z = layers.rmsnorm(h, p_l["ffn_norm"], cfg.norm_eps)
                if cfg.is_moe:
                    z, _ = moe_mod.moe_forward(p_l["moe"], cfg, z)
                else:
                    z = mlp_mod.mlp_forward(p_l["mlp"], cfg, z)
                h = h + z
                return (h, c), None

            idx = jnp.arange(cache["layers"]["lens"].shape[0],
                             dtype=jnp.int32)
            xs = ((params["layers"], idx, cache["cross_k"],
                   cache["cross_v"]) if cross
                  else (params["layers"], idx))
            (x, new_cache["layers"]), _ = _layer_scan(
                body, (x, cache["layers"]), xs, cfg.unroll_scans)

        x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = layers.unembed(params, x, cfg)[:, 0]
        return logits[:, :cfg.vocab_size], new_cache

    # -- single-request reference loop (serving oracle) --------------------------
    def reference_decode(self, params, prompt, max_new_tokens: int,
                         eos_id: int = -1, *, max_len: int = 64,
                         stepwise_prefill: bool = True, batch: int = 1):
        """Greedy single-request decode loop — the oracle the serving
        property suite holds the continuous-batching engine to.

        ``stepwise_prefill=True`` feeds the prompt one token at a time
        through ``decode_step`` (exactly the engine's chunked-admission
        path, and shape-stable: one compiled (1, 1) step serves every
        prompt length); ``False`` uses the fused ``prefill`` (the
        stall-admission path). Returns the generated token list
        (first sampled token included, stops at EOS / max_new).

        ``batch`` runs the request in every row of a ``batch``-row step
        and reads row 0. Rows never mix, but a bf16 matmul can round
        differently at a different batch width, and a near-tie in the
        logits then flips a greedy token; an oracle compiled at the
        engine's slot count rules that out.
        """
        if max_new_tokens <= 0:
            return []                # zero budget: nothing to generate
        if not hasattr(self, "_ref_jits"):
            self._ref_jits = (jax.jit(self.prefill),
                              jax.jit(self.decode_step))
        pre, dec = self._ref_jits
        cache = self.init_cache(batch, max_len)
        prompt = jnp.asarray(prompt, jnp.int32)
        if stepwise_prefill:
            for t in prompt:
                logits, cache = dec(params, jnp.full((batch, 1), t),
                                    cache)
        else:
            logits, cache = pre(
                params, {"tokens": jnp.tile(prompt[None], (batch, 1))},
                cache)
        out = [int(jnp.argmax(logits[0]))]
        while len(out) < max_new_tokens and out[-1] != eos_id:
            logits, cache = dec(
                params, jnp.full((batch, 1), out[-1], jnp.int32), cache)
            out.append(int(jnp.argmax(logits[0])))
        return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _stack_pytrees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _freeze_rows(c_new, c_old, mask):
    """Length-frozen cache write mask for the recurrent families (ssm,
    hybrid): batch rows where ``mask`` is False keep ``c_old``. Their
    per-layer cache leaves (SSM conv/state, RG-LRU conv/state, local
    attention k/v/lens) carry batch on axis 0, so one broadcast select
    covers them all. Attention-family caches freeze at the write point
    instead (``attention.kv_cache_write``)."""
    if mask is None:
        return c_new
    def sel(n, o):
        m = mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree_util.tree_map(sel, c_new, c_old)


def _prepend_prefix(prefix, x):
    """Prepend VLM patch embeddings via dynamic_update_slice into a
    padded buffer. A plain concatenate of two differently-sized pieces
    along a sharded sequence axis sends the SPMD partitioner into an
    'involuntary full rematerialization' corner (replicates the whole
    activations); DUS into one buffer partitions cleanly."""
    B, S, D = x.shape
    P_len = prefix.shape[1]
    buf = jnp.zeros((B, P_len + S, D), x.dtype)
    buf = jax.lax.dynamic_update_slice(buf, prefix.astype(x.dtype),
                                       (0, 0, 0))
    buf = jax.lax.dynamic_update_slice(buf, x, (0, P_len, 0))
    return buf, P_len


def _layer_scan(body, carry, xs, unroll: bool):
    """lax.scan over stacked layer params/caches, or a python loop when
    ``unroll`` (cost-calibration mode — while bodies are counted once by
    XLA cost_analysis, so the dry-run unrolls to get true totals)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
    else:
        stacked = None
    return carry, stacked


def _write_prefill_kv(c_l, k, v, total_len: int, seq_lens=None,
                      kv_quant: str = "bf16", group: int = 32):
    """Write prefill K/V (B, Hkv, S, hd) into the cache (ring-aware).

    ``seq_lens`` (B,) — per-sequence true lengths for right-padded
    batches; only valid on the non-ring path (padded prompts never
    exceed the cache window; the engine guarantees this).

    Quantized caches (``k_scale`` leaf present) quantize the whole
    prefill block at the write point — per-position groupwise along
    head_dim, so the rows written here are bit-identical to what the
    stepwise ``decode_step`` path would have written one at a time
    (each position's scale depends only on its own values).
    """
    from repro.quant.quantize import quantize_rows
    if "k_scale" in c_l:
        kq, ks = quantize_rows(k, kv_quant, group)
        vq, vs = quantize_rows(v, kv_quant, group)
        updates = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        updates = {"k": k, "v": v}
    S_cache = c_l["k"].shape[2]
    S = k.shape[2]
    new = {}
    for name, arr in updates.items():
        tgt = c_l[name]
        arr = arr.astype(tgt.dtype)
        if S <= S_cache:
            new[name] = jax.lax.dynamic_update_slice(
                tgt, arr, (0,) * tgt.ndim)
        else:
            # keep the last S_cache entries, at slot = pos % S_cache
            new[name] = jnp.roll(arr[:, :, -S_cache:],
                                 total_len % S_cache, axis=2)
    if S > S_cache:
        seq_lens = None        # ring path is uniform-length by contract
    adv = total_len if seq_lens is None else seq_lens
    return dict(c_l, **new, lens=c_l["lens"] + adv)


# ---------------------------------------------------------------------------
# Dry-run input specs (deliverable e/f)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, seq_len: int, global_batch: int,
                kind: str) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input.

    train → {tokens, labels}; prefill → {tokens, (frames|prefix)};
    decode → {tokens (B,1)} (cache comes from ``Model.init_cache``
    abstractified separately). Audio/VLM frontends are stubs: the
    specs provide the precomputed embeddings directly (the one
    carve-out to "no stubs").
    """
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    if kind == "train":
        out = {"tokens": jax.ShapeDtypeStruct((global_batch, seq_len), i32),
               "labels": jax.ShapeDtypeStruct((global_batch, seq_len), i32)}
    elif kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((global_batch, seq_len), i32)}
    elif kind == "decode":
        out = {"tokens": jax.ShapeDtypeStruct((global_batch, 1), i32)}
    else:
        raise ValueError(kind)
    if cfg.arch_type == "audio" and kind in ("train", "prefill"):
        out["frames"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.encoder_seq_len, cfg.d_model), bf16)
    if cfg.arch_type == "vlm" and kind in ("train", "prefill"):
        out["prefix"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.num_prefix_embeddings, cfg.d_model), bf16)
    return out
