"""GQA attention block: fused QKV, RoPE, chunked flash-style attention,
KV cache (full, or ring-buffer for sliding-window archs).

Two attention paths:

- ``chunked_attention`` — pure-jnp online-softmax over KV blocks with a
  static Python loop over Q blocks. GSPMD partitions it transparently
  (batch/heads/seq shardable), it never materializes the (Sq, Skv)
  score matrix, and causal/window *block skipping* is static — q-chunk i
  only scans KV blocks it can see, making windowed prefill linear. This
  is the default path and what the dry-run lowers.
- Pallas ``flash_attention`` / ``decode_attention`` (kernels/) — the
  TPU hot path, selected by ``cfg.use_pallas`` for single-shard or
  shard_map execution; validated against the same oracle.

Self-attention projects q|k|v in one wide GEMM (``wqkv``);
cross-attention keeps separate ``wq``/``wk``/``wv``, because its K and V
come from the encoder output.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models import layers
from repro.models.params import ParamSpec
from repro.quant.quantize import (dequantize_rows, kv_group_size,
                                  quantize_rows)


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def attention_specs(cfg: ModelConfig, cross: bool = False) -> Dict:
    D, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    specs: Dict = {}
    if not cross:
        specs["wqkv"] = layers.linear_spec(
            D, qd + 2 * kvd, ("embed", "qkv_fused"), bias=cfg.qkv_bias,
            bias_axis="qkv_fused")
    else:
        specs["wq"] = layers.linear_spec(D, qd, ("embed", "heads"),
                                         bias=cfg.qkv_bias,
                                         bias_axis="heads")
        specs["wk"] = layers.linear_spec(D, kvd, ("embed", "heads"),
                                         bias=cfg.qkv_bias,
                                         bias_axis="heads")
        specs["wv"] = layers.linear_spec(D, kvd, ("embed", "heads"),
                                         bias=cfg.qkv_bias,
                                         bias_axis="heads")
    specs["wo"] = layers.linear_spec(qd, D, ("heads", "embed"))
    return specs


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (pure jnp, shardable)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      q_offset: int = 0, bq: int = 512, bk: int = 512,
                      scale: Optional[float] = None,
                      unroll: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) → (B, Hq, Sq, D).

    Static Q-chunk loop with per-chunk static KV bounds (causal/window
    block skip); inner lax.scan over KV chunks with online softmax.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(bq, Sq)
    while Sq % bq:
        bq //= 2
    bk = min(bk, Skv)
    while Skv % bk:
        bk //= 2

    # NB: no astype(f32) on k/v — an elementwise convert of the whole
    # cache gets hoisted out of scan-over-layers loops by XLA, doubling
    # HBM. Mixed matmuls with preferred_element_type keep reads at bf16.
    qg = ((q.astype(jnp.float32) * scale).astype(q.dtype)
          .reshape(B, Hkv, G, Sq, D))
    kf = k
    vf = v

    outs = []
    for i in range(Sq // bq):
        q_i = qg[:, :, :, i * bq:(i + 1) * bq]       # (B,Hkv,G,bq,D)
        q_lo = i * bq + q_offset
        q_hi = q_lo + bq - 1
        # static KV bounds for this q chunk
        hi = min(Skv, q_hi + 1) if causal else Skv
        lo = max(0, q_lo - window + 1) if window else 0
        lo_b = (lo // bk) * bk
        hi_b = min(Skv, ((hi + bk - 1) // bk) * bk)
        n_blk = (hi_b - lo_b) // bk
        k_i = kf[:, :, lo_b:hi_b].reshape(B, Hkv, n_blk, bk, D)
        v_i = vf[:, :, lo_b:hi_b].reshape(B, Hkv, n_blk, bk, D)
        k_i = jnp.moveaxis(k_i, 2, 0)                # (n_blk,B,Hkv,bk,D)
        v_i = jnp.moveaxis(v_i, 2, 0)

        qpos = q_lo + jnp.arange(bq)

        def step(carry, inp):
            m, l, acc = carry
            k_c, v_c, blk = inp
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_i, k_c,
                           preferred_element_type=jnp.float32)
            kpos = lo_b + blk * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, -1))
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, -1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v_c.dtype), v_c,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        init = (jnp.full((B, Hkv, G, bq), NEG_INF, jnp.float32),
                jnp.zeros((B, Hkv, G, bq), jnp.float32),
                jnp.zeros((B, Hkv, G, bq, D), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            step, init, (k_i, v_i, jnp.arange(n_blk)), unroll=unroll)
        l = jnp.where(l == 0.0, 1.0, l)
        outs.append(acc / l[..., None])

    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block forward
# ---------------------------------------------------------------------------

def _split_qkv(cfg: ModelConfig, p, x, use_pallas: bool):
    """q, k, v of ``x``; cross-attention (no ``wqkv``) projects q alone
    and returns None for k and v, which its caller reads from the
    encoder."""
    if "wqkv" not in p:
        return layers.linear(p["wq"], x, use_pallas=use_pallas), None, None
    qd, kvd = cfg.q_dim, cfg.kv_dim
    qkv = layers.linear(p["wqkv"], x, use_pallas=use_pallas)
    qkv = constrain(qkv, ("batch", None, "qkv_fused"))
    return qkv[..., :qd], qkv[..., qd:qd + kvd], qkv[..., qd + kvd:]


def attention_forward(p, cfg: ModelConfig, x: jax.Array, *,
                      positions: jax.Array, window: int = 0,
                      kv_override: Optional[Tuple] = None,
                      use_rope: bool = True,
                      return_kv: bool = False):
    """Full-sequence attention (training / prefill).

    x: (B, S, D_model); positions: (B, S) absolute positions.
    ``kv_override``: (k, v) in (B, Hkv, Skv, D) — cross-attention.
    ``return_kv``: also return the (roped) K/V for cache fill.
    """
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _split_qkv(cfg, p, x, cfg.use_pallas)
    q = q.reshape(B, S, H, hd)
    if kv_override is None:
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        if use_rope:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
        k = jnp.swapaxes(k, 1, 2)
        v = jnp.swapaxes(v, 1, 2)
        causal = True
    else:
        k, v = kv_override
        causal = False
    q = jnp.swapaxes(q, 1, 2)                    # (B, H, S, hd)
    q = constrain(q, ("batch", "heads", None, None))
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            q_offset=0, bq=cfg.attn_block,
                            bk=cfg.attn_block, unroll=cfg.unroll_scans)
    out = jnp.swapaxes(out, 1, 2).reshape(B, S, H * hd)
    out = constrain(out, ("batch", None, "heads"))
    out = layers.linear(p["wo"], out, use_pallas=cfg.use_pallas)
    if return_kv:
        return out, k, v
    return out


def attention_decode(p, cfg: ModelConfig, x: jax.Array, cache: Dict, *,
                     layer=None, window: int = 0,
                     kv_override: Optional[Tuple] = None,
                     use_rope: bool = True,
                     write_mask: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, Dict]:
    """One-token decode with functional cache update.

    x: (B, 1, D); cache: {"k": (B,Hkv,S,hd), "v": ..., "lens": (B,)},
    or the model's layer-stacked cache (every leaf with a leading L)
    with ``layer`` (int32 scalar) naming this layer. A stacked cache is
    updated in place, one row per slot, and the attention kernels read
    the stacked buffer by layer index: the decode layer scan carries one
    cache buffer, and no layer's cache is ever copied out of it.

    ``lens`` counts tokens already in the cache; the new token is
    written at slot ``lens % S`` (ring buffer when the cache is a
    sliding window). Paged caches (``"block_table"`` present) route the
    write through the slot's block table instead. ``write_mask`` (B,)
    bool freezes rows where it is False at the write point: their cache
    leaves and ``lens`` stay bit-identical (see ``kv_cache_write``).
    """
    B = x.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _split_qkv(cfg, p, x, cfg.use_pallas)
    q = q.reshape(B, H, hd)

    if kv_override is not None:
        k_all, v_all = kv_override
        q = constrain(q, ("batch", "heads", None))
        out = ops.decode_attention(
            q, k_all, v_all, kv_len=cache["cross_lens"],
            use_pallas=cfg.use_pallas)
        out = out.reshape(B, 1, H * hd)
        return layers.linear(p["wo"], out, use_pallas=cfg.use_pallas), cache

    at = () if layer is None else (layer,)
    lens = cache["lens"][at]                      # (B,) int32
    paged = "block_table" in cache
    S_cache = (cache["block_table"].shape[-1] * cache["k"].shape[-2]
               if paged else cache["k"].shape[-2])
    kv_quant = cfg.kv_quant if "k_scale" in cache else "bf16"
    quantized = kv_quant in ("q8_0", "q4_0")
    pos = lens                                    # new token's position
    if use_rope:
        # pin the rope operands before the cos/sin broadcast-mul: on big
        # fake-device meshes GSPMD otherwise picks a degenerate sharding
        # for the broadcast (model axis onto the hd/2 dim) and dies with
        # an involuntary-full-rematerialization error
        q = constrain(q, ("batch", "heads", None))
        k = constrain(k.reshape(B, Hkv, hd), ("batch", None, None))
        # q (B,H,hd) → (B,1,H,hd) with positions (B,1)
        q = layers.apply_rope(q[:, None], pos[:, None],
                              cfg.rope_theta)[:, 0]
        k = layers.apply_rope(k[:, None], pos[:, None],
                              cfg.rope_theta)[:, 0]
    else:
        k = k.reshape(B, Hkv, hd)
    v = v.reshape(B, Hkv, hd)
    slot = lens % S_cache
    adv = lens + 1 if write_mask is None else jnp.where(write_mask,
                                                         lens + 1, lens)
    new_cache = dict(cache, lens=adv if layer is None
                     else cache["lens"].at[layer].set(adv))
    new_cache.update(kv_cache_write(cache, k, v, slot,
                                    kv_quant=kv_quant,
                                    group=cfg.quant_group,
                                    write_mask=write_mask, layer=layer))
    kv_len = jnp.minimum(lens + 1, S_cache)
    q = constrain(q, ("batch", "heads", None))
    names = ("k", "k_scale", "v", "v_scale") if quantized else ("k", "v")
    if paged:
        # gather this layer's pool into the dense (B, Hkv, S, ·) kernel
        # shape; positions past kv_len hold garbage-block junk that the
        # kernels' kpos < kv_len mask never reads
        tbl = cache["block_table"][at]
        leaves = [paged_gather(new_cache[n][at], tbl) for n in names]
        read_layer = None
    else:
        leaves = [new_cache[n] for n in names]
        read_layer = layer
    if quantized:
        # Fused-dequant path: the raw int8 payload + scale leaves go to
        # the kernel layer, which dequantizes in-register under
        # kernels="pallas" (no per-token full-cache unpack).
        out = ops.decode_attention_quant(
            q, *leaves, kv_len=kv_len, fmt=kv_quant, layer=read_layer,
            use_pallas=cfg.use_pallas)
    else:
        out = ops.decode_attention(q, *leaves, kv_len=kv_len,
                                   layer=read_layer,
                                   use_pallas=cfg.use_pallas)
    out = out.reshape(B, 1, H * hd)
    out = layers.linear(p["wo"], out, use_pallas=cfg.use_pallas)
    return out, new_cache


def kv_cache_write(cache: Dict, k: jax.Array, v: jax.Array,
                   slot: jax.Array, *, kv_quant: str = "bf16",
                   group: int = 32,
                   write_mask: Optional[jax.Array] = None,
                   layer=None) -> Dict:
    """Write one (B, Hkv, hd) K/V row at per-row ring ``slot`` (B,).

    With ``layer`` the cache leaves are layer-stacked and the rows land
    at ``[layer, b, :, slot]``, in place: nothing else of the cache is
    touched. The scatter indexes each (slot, head) row on its own, so
    its window is head_dim alone and the cache keeps the layout the
    attention kernels read (a (head, head_dim) window makes XLA lay the
    cache out heads-minor and copy all of it back for every kernel).

    Quantized caches (``kv_quant`` q8_0/q4_0) quantize the row at the
    write point — int8 payload into ``k``/``v``, per-(head, group)
    scales into the sibling ``k_scale``/``v_scale`` leaves — so the
    cache stream shrinks to bits/16 of its bf16 footprint. Returns the
    updated leaves only (caller merges + advances ``lens``).

    The scale rows of a dense cache are written by one
    ``dynamic_update_slice`` per slot instead: they are narrower than a
    lane tile (4 scales a head of 128 in groups of 32), and a scatter
    into such a leaf makes XLA lay it out sequence-minor and copy all of
    it back around every kernel call (six whole-leaf copies a layer at
    40 layers, one slot and 8192 positions).

    Rows with ``write_mask`` False are frozen here: a dense cache gets
    the row's old value written back at its slot; a paged pool has no
    per-slot rows, so those rows' writes go to the reserved garbage
    block 0 instead. Paged caches write through the slot's row of
    ``block_table``: position ``slot`` lands in page ``slot // P`` at
    in-page offset ``slot % P`` of the pool block that entry names."""
    B, Hkv = k.shape[:2]
    at = () if layer is None else (layer,)
    if kv_quant in ("bf16", "f16", "f32"):
        rows = {"k": k, "v": v}
    else:
        kq, ks = quantize_rows(k, kv_quant, group)
        vq, vs = quantize_rows(v, kv_quant, group)
        rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    paged = "block_table" in cache
    if paged:
        P = cache["k"].shape[-2]
        blk = cache["block_table"][at][jnp.arange(B), slot // P]
        if write_mask is not None:
            blk = jnp.where(write_mask, blk, 0)
        idx = at + (blk[:, None], jnp.arange(Hkv), (slot % P)[:, None])
    else:
        idx = at + (jnp.arange(B)[:, None], jnp.arange(Hkv),
                    slot[:, None])
    out = {}
    for name, row in rows.items():
        leaf = cache[name]
        row = row.astype(leaf.dtype)
        if paged or not name.endswith("_scale"):
            if write_mask is not None and not paged:
                row = jnp.where(write_mask[:, None, None], row, leaf[idx])
            # frozen paged rows may collide in the garbage block
            out[name] = leaf.at[idx].set(row, unique_indices=not paged)
            continue
        shape = (1,) * (len(at) + 1) + (Hkv, 1, row.shape[-1])
        for b in range(B):
            start = at + (b, 0, slot[b], 0)
            new = row[b].reshape(shape)
            if write_mask is not None:
                new = jnp.where(write_mask[b], new,
                                jax.lax.dynamic_slice(leaf, start, shape))
            leaf = jax.lax.dynamic_update_slice(leaf, new, start)
        out[name] = leaf
    return out


def paged_gather(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Gather a block pool into its dense per-slot view.

    pool: (num_blocks, Hkv, P, d); table: (B, n_pages) int32 →
    (B, Hkv, n_pages * P, d). Unmapped table entries point at the
    garbage block 0; callers mask those positions via kv_len."""
    B, n_pages = table.shape
    _, Hkv, P, d = pool.shape
    g = pool[table]                       # (B, n_pages, Hkv, P, d)
    return jnp.moveaxis(g, 1, 2).reshape(B, Hkv, n_pages * P, d)


def kv_cache_read(cache: Dict, *, kv_quant: str = "bf16",
                  dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
    """The attention-visible (B, Hkv, S, hd) K/V view of a cache.

    bf16 caches return their leaves as-is; quantized caches dequantize
    payload × scales at the read point, materializing a bf16 view.
    The decode path does not use this — ``attention_decode`` hands the
    cache leaves to the kernels (in-VMEM dequant under
    kernels="pallas"); this helper is for tests and offline inspection
    of cache contents. Paged caches gather their pools through the
    block table first, so the returned view is shape-identical to a
    dense cache's."""
    if "block_table" in cache:
        tbl = cache["block_table"]
        k = paged_gather(cache["k"], tbl)
        v = paged_gather(cache["v"], tbl)
        if kv_quant in ("bf16", "f16", "f32"):
            return k, v
        return (dequantize_rows(k, paged_gather(cache["k_scale"], tbl),
                                kv_quant, dtype),
                dequantize_rows(v, paged_gather(cache["v_scale"], tbl),
                                kv_quant, dtype))
    if kv_quant in ("bf16", "f16", "f32"):
        return cache["k"], cache["v"]
    return (dequantize_rows(cache["k"], cache["k_scale"], kv_quant, dtype),
            dequantize_rows(cache["v"], cache["v_scale"], kv_quant, dtype))


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: int = 0, dtype=jnp.bfloat16,
                  kv_quant: str = "bf16", page_size: int = 0,
                  num_blocks: int = 0) -> Dict:
    """Cache shapes; ``window`` > 0 caps the cache (ring buffer).

    ``kv_quant`` q8_0/q4_0 stores K/V as int8 payload (q4_0
    nibble-packed along head_dim) plus groupwise ``k_scale``/``v_scale``
    leaves — every leaf still carries batch on axis 0 and the ring
    position on axis 2, so the frozen-write mask, megastep donation and
    prefill splicing treat them like any other cache leaf.

    ``page_size`` > 0 pages the cache instead: K/V (and scale) leaves
    become block *pools* of shape (num_blocks, Hkv, page_size, ·) with
    no batch axis, and a per-slot ``block_table`` (batch, max_pages)
    int32 leaf maps logical pages onto pool blocks. Block 0 is reserved
    as the garbage block (frozen-row writes and unmapped table entries
    land there); ``num_blocks`` defaults to one block per logical page
    per slot plus the garbage block — capacity-equivalent to dense —
    but can be set lower so total memory tracks live tokens. Paging
    requires full attention (``window == 0``) and ``page_size`` dividing
    the sequence capacity so the gathered view is shape-identical to a
    dense cache."""
    S = min(max_len, window) if window else max_len
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    quantized = kv_quant not in ("bf16", "f16", "f32")
    if quantized:
        g = kv_group_size(hd, cfg.quant_group, kv_quant)
        pd = hd // 2 if kv_quant == "q4_0" else hd
    if page_size:
        if window:
            raise ValueError(
                "paged KV cache requires full attention (window == 0); "
                f"got window={window}")
        if S % page_size:
            raise ValueError(
                f"page_size {page_size} must divide the cache length {S}")
        max_pages = S // page_size
        n_blocks = num_blocks if num_blocks else batch * max_pages + 1
        cache = {
            "k": jnp.zeros((n_blocks, Hkv, page_size,
                            pd if quantized else hd),
                           jnp.int8 if quantized else dtype),
            "v": jnp.zeros((n_blocks, Hkv, page_size,
                            pd if quantized else hd),
                           jnp.int8 if quantized else dtype),
            "block_table": jnp.zeros((batch, max_pages), jnp.int32),
            "lens": jnp.zeros((batch,), jnp.int32),
        }
        if quantized:
            cache["k_scale"] = jnp.zeros(
                (n_blocks, Hkv, page_size, hd // g), dtype)
            cache["v_scale"] = jnp.zeros(
                (n_blocks, Hkv, page_size, hd // g), dtype)
        return cache
    if not quantized:
        return {
            "k": jnp.zeros((batch, Hkv, S, hd), dtype),
            "v": jnp.zeros((batch, Hkv, S, hd), dtype),
            "lens": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, Hkv, S, pd), jnp.int8),
        "v": jnp.zeros((batch, Hkv, S, pd), jnp.int8),
        "k_scale": jnp.zeros((batch, Hkv, S, hd // g), dtype),
        "v_scale": jnp.zeros((batch, Hkv, S, hd // g), dtype),
        "lens": jnp.zeros((batch,), jnp.int32),
    }


def kv_cache_axes(kv_quant: str = "bf16", paged: bool = False) -> Dict:
    if paged:
        # pool leaves carry the block id on axis 0 — deliberately NOT
        # "batch": splice/merge machinery keys on the "batch"
        # axis name and must leave pools untouched.
        pool = ("kv_block", None, "kv_page", None)
        axes = {"k": pool, "v": pool,
                "block_table": ("batch", None),
                "lens": ("batch",)}
        if kv_quant not in ("bf16", "f16", "f32"):
            axes["k_scale"] = pool
            axes["v_scale"] = pool
        return axes
    axes = {"k": ("batch", None, "kv_seq", None),
            "v": ("batch", None, "kv_seq", None),
            "lens": ("batch",)}
    if kv_quant not in ("bf16", "f16", "f32"):
        axes["k_scale"] = ("batch", None, "kv_seq", None)
        axes["v_scale"] = ("batch", None, "kv_seq", None)
    return axes
