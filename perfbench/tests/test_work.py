"""The FLOP and byte functions against hand arithmetic, at the
published widths of both configurations."""
import json

import pytest

from perfbench import spec, work


def conf(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


DEEPSEEK = conf("deepseek-7b.q8_0")
NEMO = conf("mistral-nemo-12b.q4_0")


def test_deepseek_weight_bytes():
    # per layer: 4096*(4096+2*4096) + 4096*4096 + 4096*2*11008
    # + 11008*4096 = 202,375,168 weights; q8_0 stores 1 + 2/32 bytes each
    layer = 4096 * 12288 + 4096 * 4096 + 4096 * 22016 + 11008 * 4096
    assert layer == 202_375_168
    b = work.weight_bytes(DEEPSEEK)
    assert b["layers"] == 30 * layer * 1.0625 == 6_450_708_480
    assert b["lm_head"] == 4096 * 102400 * 1.0625 == 445_644_800
    assert b["embedding"] == 4096 * 102400 * 2 == 838_860_800


def test_nemo_weight_bytes():
    # q_dim 4096 under d_model 5120, 8 kv heads of 128
    layer = 5120 * 6144 + 4096 * 5120 + 5120 * 28672 + 14336 * 5120
    b = work.weight_bytes(NEMO)
    assert b["layers"] == 40 * layer * 0.5625 == pytest.approx(6.13e9,
                                                              rel=1e-3)
    assert b["lm_head"] == 5120 * 131072 * 0.5625
    assert b["embedding"] == 5120 * 131072 * 2


def test_kv_bytes_per_token():
    # bf16 MHA: K and V, 32 heads of 128, 2 bytes, 30 layers
    assert work.kv_bytes_per_token(DEEPSEEK) == 2 * 32 * 128 * 2 * 30 \
        == 491_520
    # q8_0 GQA: 8 heads, 128 payload bytes + 4 bf16 scales, K and V
    assert work.kv_bytes_per_token(NEMO) == 2 * 8 * (128 + 4 * 2) * 40 \
        == 87_040


def test_quant_matmul_cost():
    flops, nbytes = work.quant_matmul(16, 4096, 22016, "q8_0")
    assert flops == 2 * 16 * 4096 * 22016
    assert nbytes == 4096 * 22016 * 1.0625 + 16 * 4096 * 2 + 16 * 22016 * 2
    _, q4 = work.quant_matmul(1, 5120, 28672, "q4_0")
    assert q4 == 5120 * 28672 * 0.5625 + 5120 * 2 + 28672 * 2


def test_decode_attention_cost_counts_live_positions():
    flops, nbytes = work.decode_attention(1000, DEEPSEEK)
    assert flops == 4 * 32 * 128 * 1000
    assert nbytes == 1000 * 16384       # one layer's K and V, bf16
    _, q8 = work.decode_attention(10, NEMO)
    assert q8 == 10 * 2 * 8 * 136


def test_token_flops():
    layer = 202_375_168
    assert work.token_flops(DEEPSEEK, 0) == 2 * (30 * layer + 4096 * 102400)
    assert work.token_flops(DEEPSEEK, 100) - work.token_flops(DEEPSEEK, 0) \
        == 30 * 4 * 32 * 128 * 100


def test_least_time_and_peaks():
    peaks = work.load_peaks("TPU v5 lite")
    assert peaks["bf16_flop_per_s"] == 197e12
    assert peaks["hbm_byte_per_s"] == 819e9
    # memory-bound GEMV: the bytes set the time
    assert work.least_time(2e9, 819e9, peaks) == pytest.approx(1.0)
    assert work.least_time(197e12, 1.0, peaks) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")
