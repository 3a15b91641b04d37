"""Per-kernel interpret-mode allclose sweeps vs the ref.py oracles."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant_matmul import quant_matmul
from repro.quant import dequantize, quantize_q4_0, quantize_q8_0


# ---------------------------------------------------------------------------
# quant_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mkn", [(128, 256, 128), (8, 64, 128),
                                 (256, 512, 384), (64, 1024, 64)])
@pytest.mark.parametrize("quant", [quantize_q8_0, quantize_q4_0])
@pytest.mark.parametrize("xdtype", [jnp.bfloat16, jnp.float32])
def test_quant_matmul_allclose(mkn, quant, xdtype):
    M, K, N = mkn
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (M, K), jnp.float32).astype(xdtype)
    w = quant(jax.random.normal(k2, (K, N), jnp.float32))
    bm, bn, bk = min(128, M), min(128, N), min(256, K)
    out = quant_matmul(x, w, bm=bm, bn=bn, bk=bk, interpret=True,
                       out_dtype=jnp.float32)
    want = ref.quant_matmul_ref(x, w, out_dtype=jnp.float32)
    scale = np.abs(np.asarray(want)).max() + 1e-6
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=0.02 * scale, rtol=0.05)


@pytest.mark.parametrize("m", [1, 2, 3, 4])   # decode: M = active slots
@pytest.mark.parametrize("kn", [(32, 48),     # single group, N unaligned
                                (96, 80),     # K, N both non-128-aligned
                                (64, 128)])   # group boundary K
@pytest.mark.parametrize("quant,fmt_tol",
                         [(quantize_q8_0, 0.05), (quantize_q4_0, 0.25)])
def test_quant_matmul_decode_shapes(m, kn, quant, fmt_tol):
    """Serving decode GEMVs: tiny M (one row per active slot),
    group-boundary and non-128-aligned K/N. Checked two ways: against
    the dequantize+einsum reference (near-exact — the kernel performs
    the same dequant arithmetic in f32) and against the *unquantized*
    matmul with per-format tolerances (the §5.3 quality cost)."""
    K, N = kn
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(k1, (m, K), jnp.float32)
    wf = jax.random.normal(k2, (K, N), jnp.float32)
    w = quant(wf)
    out = quant_matmul(x, w, bm=m, bn=N, bk=K, interpret=True,
                       out_dtype=jnp.float32)
    want = ref.quant_matmul_ref(x, w, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    exact = np.asarray(jnp.einsum("mk,kn->mn", x, wf))
    scale = np.abs(exact).max() + 1e-6
    np.testing.assert_allclose(np.asarray(out), exact,
                               atol=fmt_tol * scale)


@pytest.mark.parametrize("quant", [quantize_q8_0, quantize_q4_0])
def test_quant_matmul_stacked_layer_slices(quant):
    """Scan-over-layers serving path: a stacked (L, K, N) quantized
    weight sliced per layer must matmul identically to quantizing each
    layer independently (slicing only drops the leading dim — data,
    scales and the derived logical shape all stay consistent)."""
    L, M, K, N = 3, 2, 64, 48
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.normal(k1, (M, K), jnp.float32)
    wf = jax.random.normal(k2, (L, K, N), jnp.float32)
    stacked = quant(wf)
    for i in range(L):
        w_i = jax.tree_util.tree_map(lambda a: a[i], stacked)
        assert w_i.logical_shape == (K, N)
        out = quant_matmul(x, w_i, bm=M, bn=N, bk=K, interpret=True,
                           out_dtype=jnp.float32)
        want = quant_matmul(x, quant(wf[i]), bm=M, bn=N, bk=K,
                            interpret=True, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m", [1, 8])
@pytest.mark.parametrize("bk", [256, 512, 1024])
@pytest.mark.parametrize("parity", [0, 1])
def test_quant_matmul_q4_0_nibble_meets_its_column(parity, bk, m):
    """q4_0 pairs the low nibble plane with the even activation columns
    of a K-block and the high plane with the odd ones. With one non-zero
    logical row k the output is exactly x[:, k] * w[k, :]: a swap of the
    planes or a permutation inside a block picks another column of x."""
    K, N = 1024, 256
    kx, kw = jax.random.split(jax.random.PRNGKey(41))
    x = jax.random.normal(kx, (m, K), jnp.float32).astype(jnp.bfloat16)
    row = jax.random.normal(kw, (N,), jnp.float32)
    for k in (parity, 130 + parity, 516 + parity, K - 2 + parity):
        w = quantize_q4_0(jnp.zeros((K, N), jnp.float32).at[k].set(row))
        out = quant_matmul(x, w, bm=m, bn=N, bk=bk, interpret=True,
                           out_dtype=jnp.float32)
        w_k = dequantize(w, jnp.bfloat16)[k].astype(jnp.float32)
        want = x[:, k].astype(jnp.float32)[:, None] * w_k[None, :]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want),
                                      err_msg=f"row {k}")


def test_quant_matmul_grid_tiling_exact():
    """Tiling must not change results vs a single-tile call."""
    M, K, N = 256, 512, 256
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (M, K), jnp.float32)
    w = quantize_q8_0(jax.random.normal(k2, (K, N), jnp.float32))
    a = quant_matmul(x, w, bm=64, bn=64, bk=128, interpret=True,
                     out_dtype=jnp.float32)
    b = quant_matmul(x, w, bm=256, bn=256, bk=512, interpret=True,
                     out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

CASES = [
    # B, Hq, Hkv, Sq, Skv, D, window, q_offset
    (2, 4, 2, 256, 256, 64, 0, 0),
    (1, 8, 1, 128, 128, 32, 0, 0),       # MQA
    (2, 4, 4, 256, 256, 64, 64, 0),      # sliding window
    (1, 2, 1, 128, 256, 64, 0, 128),     # q offset (chunked prefill)
    (1, 2, 2, 64, 64, 128, 16, 0),       # tiny window
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_allclose(case, dtype):
    B, Hq, Hkv, Sq, Skv, D, win, off = case
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=True, window=win, q_offset=off,
                          bq=64, bk=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=win,
                             q_offset=off)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@given(st.integers(0, 2**31), st.sampled_from([32, 64]),
       st.sampled_from([1, 2, 4]))
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(seed, bq, g):
    """Property: rows attend only within the causal window — permuting
    *future* keys never changes the output."""
    B, Hkv, S, D = 1, 2, 128, 32
    Hq = Hkv * g
    ks = jax.random.split(jax.random.PRNGKey(seed % (2**31)), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    out = flash_attention(q, k, v, causal=True, bq=bq, bk=bq,
                          interpret=True)
    # shuffle keys in the strictly-future half for the first query row
    row = S // 2 - 1
    perm = np.arange(S)
    perm[S // 2:] = perm[S // 2:][::-1]
    out2 = flash_attention(q, k[:, :, perm], v[:, :, perm], causal=True,
                           bq=bq, bk=bq, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :, :row + 1]),
                               np.asarray(out2[:, :, :row + 1]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    (2, 8, 2, 256, 64, 0), (3, 4, 4, 512, 32, 0), (2, 8, 1, 256, 64, 128),
    (1, 16, 2, 128, 128, 0),
])
def test_decode_attention_allclose(case):
    B, Hq, Hkv, S, D, win = case
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    lens = jnp.asarray(([S // 2, S] + [S // 4] * B)[:B], jnp.int32)
    out = decode_attention(q, k, v, lens, window=win, bk=64,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, kv_len=lens, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)


def test_decode_attention_ignores_stale_cache():
    """Entries past kv_len must not affect the result."""
    B, Hq, Hkv, S, D = 2, 4, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    lens = jnp.asarray([100, 17], jnp.int32)
    out1 = decode_attention(q, k, v, lens, bk=64, interpret=True)
    k2 = k.at[:, :, 200:].set(1e4)   # poison stale region
    v2 = v.at[:, :, 200:].set(-1e4)
    out2 = decode_attention(q, k2, v2, lens, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_xla_fallback_matches_kernel():
    """ops.decode_attention's bf16 jnp path == Pallas kernel."""
    from repro.kernels import ops
    B, Hq, Hkv, S, D = 2, 8, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lens = jnp.asarray([64, 128], jnp.int32)
    a = ops.decode_attention(q, k, v, lens, use_pallas=False)
    b = ops.decode_attention(q, k, v, lens, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# Quantized KV ring buffer (cache-write point of decode_step / prefill)
# ---------------------------------------------------------------------------

def _ring_cfg(head_dim, num_kv_heads=2, quant_group=32):
    from repro.configs.base import ModelConfig
    return ModelConfig(name="ring-test", d_model=head_dim * num_kv_heads,
                       num_heads=num_kv_heads, num_kv_heads=num_kv_heads,
                       head_dim=head_dim, quant_group=quant_group)


@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
@pytest.mark.parametrize("window,head_dim", [(4, 32),   # aligned
                                             (5, 24),   # both unaligned
                                             (7, 48)])  # window > group? no:
def test_quantized_ring_wraparound_writes(fmt, window, head_dim):
    """Sliding-window ring semantics survive quantization: writing more
    rows than the window holds leaves each final slot equal to the
    round-trip quantization of the *last* row written there, including
    non-group-aligned window and head dims. The expectation quantizes
    under jit like the write path does — XLA-CPU's compiled division
    breaks exact .5 rounding ties differently from the eager op (see
    quantize_rows), and bf16 rows hit such ties routinely."""
    from repro.models import attention as attn
    from repro.quant import dequantize_rows, quantize_rows
    cfg = _ring_cfg(head_dim)
    B, Hkv, hd = 2, cfg.num_kv_heads, cfg.head_dim
    cache = attn.init_kv_cache(cfg, B, max_len=64, window=window,
                               kv_quant=fmt)
    assert cache["k"].dtype == jnp.int8
    n_writes = 3 * window  # wraps the ring twice
    rows = jax.random.normal(jax.random.PRNGKey(0),
                             (n_writes, B, Hkv, hd), jnp.bfloat16)
    write = jax.jit(lambda c, k, v, slot: attn.kv_cache_write(
        c, k, v, slot, kv_quant=fmt, group=cfg.quant_group))
    roundtrip = jax.jit(lambda x: dequantize_rows(
        *quantize_rows(x, fmt, cfg.quant_group), fmt))
    for i in range(n_writes):
        slot = jnp.full((B,), i % window, jnp.int32)
        cache = dict(cache, **write(cache, rows[i], rows[i], slot))
    k_read, _ = attn.kv_cache_read(cache, kv_quant=fmt)
    for s in range(window):
        last = n_writes - window + s  # last write landing in slot s
        np.testing.assert_array_equal(
            np.asarray(k_read[:, :, s], np.float32),
            np.asarray(roundtrip(rows[last]), np.float32))


@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
def test_quantized_prefill_write_matches_stepwise(fmt):
    """The fused-prefill cache write (_write_prefill_kv) and the
    one-row-at-a-time decode write produce bit-identical quantized
    leaves — the invariant that keeps both admission modes pinned to
    the same reference stream (each position's scale depends only on
    its own values)."""
    from repro.models import attention as attn
    from repro.models.model import _write_prefill_kv
    cfg = _ring_cfg(32)
    B, Hkv, hd, S = 2, cfg.num_kv_heads, cfg.head_dim, 6
    kv = jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, S, hd),
                           jnp.bfloat16)
    fused = attn.init_kv_cache(cfg, B, max_len=8, kv_quant=fmt)
    fused = jax.jit(lambda c, x: _write_prefill_kv(
        c, x, x, S, kv_quant=fmt, group=cfg.quant_group))(fused, kv)
    step = attn.init_kv_cache(cfg, B, max_len=8, kv_quant=fmt)
    write = jax.jit(lambda c, x, slot: attn.kv_cache_write(
        c, x, x, slot, kv_quant=fmt, group=cfg.quant_group))
    for i in range(S):
        slot = jnp.full((B,), i, jnp.int32)
        step = dict(step, **write(step, kv[:, :, i], slot))
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(fused[name][:, :, :S], np.float32),
            np.asarray(step[name][:, :, :S], np.float32), err_msg=name)


def test_bf16_ring_write_read_unchanged():
    """kv_cache_write/read on a bf16 cache are the plain set/passthrough
    the pre-kv-quant decode path used."""
    from repro.models import attention as attn
    cfg = _ring_cfg(32)
    B = 2
    cache = attn.init_kv_cache(cfg, B, max_len=4)
    row = jax.random.normal(jax.random.PRNGKey(2),
                            (B, cfg.num_kv_heads, cfg.head_dim),
                            jnp.bfloat16)
    cache = dict(cache, **attn.kv_cache_write(
        cache, row, row, jnp.zeros((B,), jnp.int32)))
    k_read, v_read = attn.kv_cache_read(cache)
    assert k_read is cache["k"] and v_read is cache["v"]
    np.testing.assert_array_equal(np.asarray(k_read[:, :, 0], np.float32),
                                  np.asarray(row, np.float32))


# ---------------------------------------------------------------------------
# decode attention over quantized cache leaves (fused dequant kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    # B, Hq, Hkv, S, D, win, group
    (2, 8, 2, 128, 64, 0, 32),    # full cache, GQA
    (2, 4, 2, 128, 24, 0, 8),     # non-group-aligned head dim
    (1, 8, 1, 256, 64, 64, 32),   # sliding window
    (3, 4, 4, 64, 32, 32, 16),    # MHA, window = ring size
])
@pytest.mark.parametrize("fmt", ["q8_0", "q4_0"])
def test_decode_attention_quant_parity(case, fmt):
    """Fused-dequant kernel == dequantize_rows + the XLA decode oracle,
    on the same quantized leaves — incl. part-filled and empty
    (kv_len=0) rows, sliding windows and non-group-aligned head dims."""
    from repro.kernels import ops
    from repro.kernels.decode_attention_quant import decode_attention_quant
    from repro.quant import quantize_rows
    B, Hq, Hkv, S, D, win, group = case
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    kf = jax.random.normal(ks[1], (B, Hkv, S, D)).astype(jnp.bfloat16)
    vf = jax.random.normal(ks[2], (B, Hkv, S, D)).astype(jnp.bfloat16)
    kq, ksc = quantize_rows(kf, fmt, group)
    vq, vsc = quantize_rows(vf, fmt, group)
    lens = jnp.asarray(([0, S // 2, S] + [S // 4] * B)[:B], jnp.int32)
    out = decode_attention_quant(q, kq, ksc, vq, vsc, lens, fmt=fmt,
                                 window=win, bk=64, interpret=True)
    want = ops.decode_attention_quant(q, kq, ksc, vq, vsc, lens,
                                      fmt=fmt, window=win,
                                      use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-5)


@pytest.mark.parametrize("fmt", ["bf16", "q8_0", "q4_0"])
@pytest.mark.parametrize("layer", [1, 2], ids=["middle", "last"])
@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "xla"])
def test_decode_attention_reads_stacked_cache_by_layer(fmt, layer,
                                                       use_pallas):
    """The decode kernels (and their XLA fallbacks) called on the
    layer-stacked (L, B, Hkv, S, D) cache with a layer index equal the
    call on that layer's own (B, Hkv, S, D) cache, bit for bit."""
    from repro.kernels import ops
    from repro.quant import quantize_rows
    L, B, Hq, Hkv, S, D = 3, 2, 8, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (B, Hq, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (L, B, Hkv, S, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (L, B, Hkv, S, D)).astype(jnp.bfloat16)
    lens = jnp.asarray([S // 2, S], jnp.int32)
    if fmt == "bf16":
        leaves = (k, v)
        call = functools.partial(ops.decode_attention,
                                 use_pallas=use_pallas)
    else:
        kq, ksc = quantize_rows(k, fmt, 32)
        vq, vsc = quantize_rows(v, fmt, 32)
        leaves = (kq, ksc, vq, vsc)
        call = functools.partial(ops.decode_attention_quant, fmt=fmt,
                                 use_pallas=use_pallas)
    got = jax.jit(lambda l, *a: call(q, *a, kv_len=lens, layer=l))(
        jnp.int32(layer), *leaves)
    want = jax.jit(lambda *a: call(q, *a, kv_len=lens))(
        *(a[layer] for a in leaves))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_attention_quant_kv_len_zero_rows():
    """A fully-empty row decodes to zeros on both paths (the l==0
    guard), while a neighbouring full row is unaffected."""
    from repro.kernels import ops
    from repro.quant import quantize_rows
    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    kf = jax.random.normal(ks[1], (B, Hkv, S, D)).astype(jnp.bfloat16)
    vf = jax.random.normal(ks[2], (B, Hkv, S, D)).astype(jnp.bfloat16)
    kq, ksc = quantize_rows(kf, "q8_0", 32)
    vq, vsc = quantize_rows(vf, "q8_0", 32)
    lens = jnp.asarray([0, S], jnp.int32)
    out = ops.decode_attention_quant(q, kq, ksc, vq, vsc, lens,
                                     fmt="q8_0", use_pallas=True)
    want = ops.decode_attention_quant(q, kq, ksc, vq, vsc, lens,
                                      fmt="q8_0", use_pallas=False)
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.zeros((Hq, D), np.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-5)


def test_decode_attention_quant_ring_wraparound_cache():
    """Quantized sliding-window ring cache: leaves written through
    kv_cache_write (wrapping the ring twice) read identically through
    the fused kernel and the dequantize_rows + oracle path."""
    from repro.kernels import ops
    from repro.models import attention as attn
    from repro.configs.base import ModelConfig
    window, hd, Hkv, B = 8, 32, 2, 2
    cfg = ModelConfig(name="ringq", d_model=hd * Hkv, num_heads=Hkv * 2,
                      num_kv_heads=Hkv, head_dim=hd, quant_group=32)
    cache = attn.init_kv_cache(cfg, B, max_len=64, window=window,
                               kv_quant="q4_0")
    n_writes = 2 * window + 3
    rows = jax.random.normal(jax.random.PRNGKey(23),
                             (n_writes, B, Hkv, hd), jnp.bfloat16)
    for i in range(n_writes):
        slot = jnp.full((B,), i % window, jnp.int32)
        cache = dict(cache, **attn.kv_cache_write(
            cache, rows[i], rows[i], slot, kv_quant="q4_0",
            group=cfg.quant_group))
    q = jax.random.normal(jax.random.PRNGKey(29),
                          (B, cfg.num_heads, hd), jnp.float32)
    lens = jnp.full((B,), window, jnp.int32)  # ring full: all slots valid
    args = (q, cache["k"], cache["k_scale"], cache["v"],
            cache["v_scale"], lens)
    out = ops.decode_attention_quant(*args, fmt="q4_0", use_pallas=True)
    want = ops.decode_attention_quant(*args, fmt="q4_0",
                                      use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-5)


def test_decode_attention_quant_rejects_bad_inputs():
    from repro.kernels.decode_attention_quant import decode_attention_quant
    q = jnp.zeros((1, 4, 32))
    kq = jnp.zeros((1, 1, 64, 32), jnp.int8)
    sc = jnp.zeros((1, 1, 64, 1), jnp.bfloat16)
    with pytest.raises(ValueError, match="fmt"):
        decode_attention_quant(q, kq, sc, kq, sc, 8, fmt="bf16")
    with pytest.raises(ValueError, match="payload"):
        # q4_0 payload should be D//2 = 16 wide, not 32
        decode_attention_quant(q, kq, sc, kq, sc, 8, fmt="q4_0")


# ---------------------------------------------------------------------------
# tile dispatch (_pick_tile / _pick_lane_tile) and env parsing
# ---------------------------------------------------------------------------

def test_pick_tile_lane_alignment():
    """Lane (minor) dims must tile 128-aligned or span the whole dim;
    the old picker handed Mosaic degenerate tiles (bn=29 for 493) that
    only worked in interpret mode."""
    from repro.kernels.ops import _pick_lane_tile, _pick_tile
    assert _pick_tile(512, 256) == 256
    assert _pick_tile(493, 128) == 29          # generic divisor picker
    assert _pick_lane_tile(493, 128) is None   # ...lane guard rejects it
    assert _pick_lane_tile(256, 128) == 128
    assert _pick_lane_tile(64, 128) == 64      # full-span, sublane-ok
    assert _pick_lane_tile(24, 128, multiple=8) == 24
    assert _pick_lane_tile(12, 128) is None    # not 8-aligned
    assert _pick_lane_tile(384, 128) == 128
    # group multiple must survive the lane constraint
    assert _pick_lane_tile(256, 256, multiple=32) == 256
    assert _pick_lane_tile(96, 128, multiple=32) == 96


@pytest.mark.parametrize("mkn", [(1, 64, 64),      # decode GEMV, bm=M=1
                                 (12, 64, 64),     # sublane-padded bm
                                 (2, 64, 93),      # prime-ish N -> XLA
                                 (3, 36, 64)])     # misaligned K -> XLA
@pytest.mark.parametrize("quant", [quantize_q8_0, quantize_q4_0])
def test_matmul_dispatch_misaligned_shapes(mkn, quant):
    """ops.matmul must stay correct whichever side of the tile-dispatch
    guard a shape lands on (fused kernel or XLA fallback)."""
    from repro.kernels import ops
    M, K, N = mkn
    k1, k2 = jax.random.split(jax.random.PRNGKey(31))
    x = jax.random.normal(k1, (M, K), jnp.float32)
    wf = jax.random.normal(k2, (K, N), jnp.float32)
    if K % 32:
        w = quant(wf, group=K)   # degenerate group for tiny K
    else:
        w = quant(wf)
    # only the decode GEMV has a fused tiling
    assert bool(ops._quant_tiles(M, K, N, w)) == (mkn == (1, 64, 64))
    out = ops.matmul(x, w, use_pallas=True, out_dtype=jnp.float32)
    want = ref.quant_matmul_ref(x, w, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# K steps of two tiles and N of four tiles at the tiles ops serves:
# q8_0 bk 512, q4_0 bk 1024 (ops.DEFAULT_BK_Q4), bn 256
@pytest.mark.parametrize("fmt,K,N", [("q8_0", 1024, 1024),
                                     ("q4_0", 2048, 1024)])
def test_unembed_quantized_head_through_kernel(fmt, K, N):
    """A quantized ``lm_head`` under kernels="pallas" streams through
    ``quant_matmul`` (interpret mode) and keeps f32 logits: they agree
    with the f32 oracle up to the bf16 rounding of the dequantized
    tiles, and pick the same token as the XLA path over the same
    bf16-rounded weights."""
    import dataclasses
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import layers
    quant = {"q8_0": quantize_q8_0, "q4_0": quantize_q4_0}[fmt]
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(k1, (3, 1, K), jnp.float32).astype(jnp.bfloat16)
    w = quant(jax.random.normal(k2, (K, N), jnp.float32))
    bk = ops.DEFAULT_BK_Q4 if fmt == "q4_0" else 512
    assert ops._quant_tiles(3, K, N, w) == (3, 256, bk)
    assert K // bk == 2
    base = get_config("deepseek-7b")
    assert not base.tie_embeddings
    pallas = dataclasses.replace(base, kernels="pallas")
    xla = dataclasses.replace(base, kernels="xla")
    out = layers.unembed({"lm_head": w}, x, pallas)
    assert out.dtype == jnp.float32 and out.shape == (3, 1, N)
    want = ref.quant_matmul_ref(x.reshape(3, K), w, out_dtype=jnp.float32)
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(out).reshape(3, N),
                               np.asarray(want), rtol=0, atol=0.01 * scale)
    rounded = layers.unembed({"lm_head": dequantize(w, jnp.bfloat16)}, x,
                             xla)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rounded),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.argmax(np.asarray(out), -1),
                                  np.argmax(np.asarray(rounded), -1))


@pytest.mark.parametrize("val,expected", [
    ("1", True), ("true", True), ("TRUE", True), ("yes", True),
    ("0", False), ("false", False), ("False", False), ("FALSE", False),
    ("no", False), ("off", False), ("OFF", False), (" 0 ", False),
    ("", False),
])
def test_interpret_default_env_parsing(val, expected, monkeypatch):
    """REPRO_PALLAS_INTERPRET=False/FALSE/no/off must disable interpret
    mode (the old truthiness check treated any non-empty string as
    enabled)."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", val)
    assert ops._interpret_default() is expected


def test_interpret_default_unset_follows_backend(monkeypatch):
    from repro.kernels import ops
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert ops._interpret_default() is (jax.default_backend() != "tpu")


@pytest.mark.parametrize("val", [None, "1", "true", "0"])
def test_tpu_backend_never_interprets(val, monkeypatch):
    """On a TPU backend the kernels compile to Mosaic whatever
    REPRO_PALLAS_INTERPRET says: the variable cannot hide the device."""
    from repro.kernels import ops
    if val is None:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", val)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._interpret_default() is False
