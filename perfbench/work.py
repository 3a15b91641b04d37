"""Operations and bytes that the algorithm needs, from shapes alone.

Weights count at their stored width (payload plus one bf16 scale per
group of 32); attention counts the positions each slot holds, not the
cache's capacity. These functions are the yardstick of every roofline
share and utilization the benchmark reports.
"""
from __future__ import annotations

import json
import pathlib
from types import SimpleNamespace
from typing import Dict, List, Tuple

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

ELEM_BYTES = {"bf16": 2, "bfloat16": 2, "f32": 4, "float32": 4}


def dims(conf: dict) -> SimpleNamespace:
    """The sizes of a configuration file, under short names."""
    hq = int(conf["num_attention_heads"])
    d = int(conf["hidden_size"])
    hd = int(conf.get("head_dim") or d // hq)
    hkv = int(conf["num_key_value_heads"])
    return SimpleNamespace(
        D=d, F=int(conf["intermediate_size"]), Hq=hq, Hkv=hkv, hd=hd,
        L=int(conf["num_hidden_layers"]), V=int(conf["vocab_size"]),
        qd=hq * hd, kvd=hkv * hd)


def bytes_per_weight(fmt: str, group: int = 32) -> float:
    if fmt == "q8_0":
        return 1.0 + 2.0 / group
    if fmt == "q4_0":
        return 0.5 + 2.0 / group
    return float(ELEM_BYTES[fmt])


def layer_matmuls(conf: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of one layer's weight matrices, in the order a layer
    runs them: fused q|k|v, output, fused gate|up, down."""
    m = dims(conf)
    return [("wqkv", m.D, m.qd + 2 * m.kvd), ("wo", m.qd, m.D),
            ("w_gate_up", m.D, 2 * m.F), ("w_down", m.F, m.D)]


def weight_bytes(conf: dict) -> Dict[str, float]:
    """Stored bytes: layer projections, ``lm_head`` and the bf16
    embedding table."""
    m = dims(conf)
    s = conf["serving"]
    bpw = bytes_per_weight(s["weights"], s["group"])
    layer = sum(k * n for _, k, n in layer_matmuls(conf))
    return {"layers": m.L * layer * bpw, "lm_head": m.D * m.V * bpw,
            "embedding": m.V * m.D * 2.0}


def kv_group(hd: int, group: int = 32) -> int:
    g = min(group, hd)
    while hd % g:
        g -= 1
    return g


def kv_bytes_per_position(conf: dict, fmt: str = None) -> float:
    """K and V bytes one cached position takes in one layer."""
    m = dims(conf)
    fmt = fmt or conf["serving"]["kv_cache"]
    if fmt in ("q8_0", "q4_0"):
        payload = m.hd if fmt == "q8_0" else m.hd // 2
        scales = m.hd // kv_group(m.hd, conf["serving"]["group"]) * 2
        return 2.0 * m.Hkv * (payload + scales)
    return 2.0 * m.Hkv * m.hd * ELEM_BYTES[fmt]


def kv_bytes_per_token(conf: dict, fmt: str = None) -> float:
    return dims(conf).L * kv_bytes_per_position(conf, fmt)


def quant_matmul(M: int, K: int, N: int, fmt: str, group: int = 32,
                 act_bytes: int = 2, out_bytes: int = 2
                 ) -> Tuple[float, float]:
    """(operations, bytes) of ``x (M, K) @ w (K, N)`` with ``w`` stored
    quantized: the stored weight, the activations in and out."""
    flops = 2.0 * M * K * N
    nbytes = (K * N * bytes_per_weight(fmt, group)
              + M * K * act_bytes + M * N * out_bytes)
    return flops, nbytes


def decode_attention(live: float, conf: dict) -> Tuple[float, float]:
    """(operations, bytes) of one layer's decode attention over ``live``
    cached positions in all, summed over the slots: q.k and p.v for
    every query head, and every live position's K and V read once."""
    m = dims(conf)
    return 4.0 * m.Hq * m.hd * live, live * kv_bytes_per_position(conf)


def token_flops(conf: dict, context: float) -> float:
    """Model operations of one token through every layer and the head,
    attending to ``context`` positions."""
    m = dims(conf)
    layer = sum(k * n for _, k, n in layer_matmuls(conf))
    return (2.0 * (m.L * layer + m.D * m.V)
            + m.L * 4.0 * m.Hq * m.hd * context)


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS_FILE.name}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at best: the larger of operations over
    the bf16 peak and bytes over the memory bandwidth."""
    return max(flops / peaks["bf16_flop_per_s"],
               nbytes / peaks["hbm_byte_per_s"])
