"""Device time of one ``quant_matmul`` call on a TPU, per call and per step.

    PYTHONPATH=src python benchmarks/quant_matmul_chip.py [--calls 200]

Cases: the four decode GEMVs of mistral-nemo-12b with q4_0 weights at
one slot (M = 1: qkv, o, gate/up, down) and, as a control, the q8_0
gate/up of deepseek-7b at 8 slots. Each case runs ``--calls`` calls in
one jitted loop; each call's activation takes a tiny multiple of the
last output, so no call is hoisted out of the loop or merged with
another. The host time of the loop, over the calls, is the time of one
call (the wrapper's ops included); over the kernel's grid steps, the
time of one step. Each case runs at the tiles ``ops.matmul`` serves it
with; ``--bk`` times the q4_0 cases at further K tiles besides. One
JSON line per case and tile. Needs a TPU: on any other backend the
kernel runs interpreted and the times mean nothing.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.quant_matmul import quant_matmul
from repro.quant import QuantizedTensor

GROUP = 32          # the q8_0 / q4_0 quant group
# (fmt, M, K, N, the call's place in the model)
CASES = [
    ("q4_0", 1, 5120, 6144, "mistral-nemo-12b qkv"),
    ("q4_0", 1, 4096, 5120, "mistral-nemo-12b o"),
    ("q4_0", 1, 5120, 28672, "mistral-nemo-12b gate/up"),
    ("q4_0", 1, 14336, 5120, "mistral-nemo-12b down"),
    ("q8_0", 8, 4096, 22016, "deepseek-7b gate/up"),
]


def _weights(fmt, K, N):
    kd, ks = jax.random.split(jax.random.PRNGKey(0))
    rows = K // 2 if fmt == "q4_0" else K
    data = jax.random.randint(kd, (rows, N), -128, 128, jnp.int32
                              ).astype(jnp.int8)
    scales = (jax.random.uniform(ks, (K // GROUP, N), jnp.float32)
              * 0.01).astype(jnp.bfloat16)
    return QuantizedTensor(data, scales, fmt, GROUP)


def time_case(w, M, bm, bn, bk, calls, repeats=5):
    fmt, data, scales = w.fmt, w.data, w.scales
    K, N = w.logical_shape
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K), jnp.float32
                          ).astype(jnp.bfloat16)

    @jax.jit
    def loop(x, data, scales):
        w = QuantizedTensor(data, scales, fmt, GROUP)

        def body(_, xc):
            y = quant_matmul(xc, w, bm=bm, bn=bn, bk=bk)
            return xc + (y[:, :1] * 1e-30).astype(xc.dtype)

        return jax.lax.fori_loop(0, calls, body, x)

    jax.block_until_ready(loop(x, data, scales))        # compile, warm
    secs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(x, data, scales))
        secs.append(time.perf_counter() - t0)
    call_us = statistics.median(secs) / calls * 1e6
    steps = (M // bm) * (N // bn) * (K // bk)
    nbytes = data.size + scales.size * 2 + x.size * 2 + M * N * 2
    return {"fmt": fmt, "M": M, "K": K, "N": N, "bn": bn, "bk": bk,
            "call_us": round(call_us, 2),
            "step_us": round(call_us / steps, 4), "steps": steps,
            "GB_per_s": round(nbytes / call_us * 1e-3, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--bk", default="",
                    help="comma-separated further K tiles for the q4_0 "
                         "cases, timed beside the served one")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("quant_matmul_chip: needs a TPU", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    more = [int(b) for b in args.bk.split(",") if b]
    for fmt, M, K, N, what in CASES:
        w = _weights(fmt, K, N)
        bm, bn, bk = ops._quant_tiles(M, K, N, w)
        tiles = [(bk, "served")] + [
            (b, "--bk") for b in more
            if fmt == "q4_0" and b != bk and K % b == 0]
        for b, why in tiles:
            row = time_case(w, M, bm, bn, b, args.calls)
            row.update(call=what, tile=why, device=dev.device_kind)
            print(json.dumps(row), flush=True)
    return 0

if __name__ == "__main__":
    sys.exit(main())
