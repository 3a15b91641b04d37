"""Chip smoke: the serving path on one TPU at llama3.2-1b's full width.

    python3 chip_smoke.py

One process, no subprocesses. Phases, each printing its own line:

  device        the first JAX device must be a TPU (no CPU fallback)
  kernels       each main-path Pallas kernel, at the model's widths and
                with the tiles kernels/ops.py picks, on bf16 inputs,
                against its kernels/ref.py oracle with the bf16
                tolerances of tests/test_kernels.py
  serve-xla     8 greedy requests through AsyncServingFrontend over the
                engine launch.serve builds (bf16 weights and cache, XLA
                kernels); tokens must equal Model.reference_decode
                compiled at the engine's slot count
  serve-pallas  the same requests under q8_0 and then q4_0 weights and
                KV cache with the Pallas kernels; the compiled megastep
                must hold the kernels (tpu_custom_call) and every
                request must finish with finite logits

Weights are random, from a fixed seed. Timings printed here are smoke
output, not benchmark numbers. The last line of standard output is a
JSON object naming the device; it is printed only if every phase passed.
"""
from __future__ import annotations

import asyncio
import gc
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import decode_attention as _da  # noqa: E402
from repro.kernels import decode_attention_quant as _daq  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels import quant_matmul as _qmm  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (AsyncServingFrontend,  # noqa: E402
                                build_engine, build_parser)
from repro.quant import (dequantize_rows, quantize_q4_0,  # noqa: E402
                         quantize_q8_0, quantize_rows)

ARCH = "llama3.2-1b"
SEED = 0
SLOTS, MAX_LEN = 4, 256
N_REQUESTS, MAX_NEW = 8, 32
PROMPT_LENS = (16, 64)          # inclusive range of prompt lengths
KERNEL_BATCH, KERNEL_CACHE = 4, 1024


def say(phase: str, msg: str) -> None:
    print(f"[smoke] {phase}: {msg}", flush=True)


class PhaseFailed(Exception):
    pass


def check_close(name, got, want, *, atol, rtol=0.0):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    if not np.all(np.isfinite(got)):
        raise PhaseFailed(f"{name}: nonfinite output")
    if not np.allclose(got, want, atol=atol, rtol=rtol):
        raise PhaseFailed(f"{name}: max |err| {err:.3g} over atol {atol:.3g}"
                          f" rtol {rtol:.3g}")
    return f"{name} max|err|={err:.3g} (atol {atol:.3g})"


# -- phases -----------------------------------------------------------------

def phase_device():
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise PhaseFailed(f"first device is {d.platform!r}, not a TPU")
    say("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _kernel_checks():
    """(name, thunk) per kernel and format, at the model's widths, on bf16
    activations and caches as the serving path feeds them. Each thunk
    compares against the f32 oracle run at full matmul precision.
    Tolerances are those tests/test_kernels.py gives bf16 inputs: a
    Mosaic dot on f32 operands runs bf16 passes by default, so the f32
    tolerances there hold only in interpret mode."""
    cfg = get_config(ARCH)
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    bf16 = jnp.bfloat16
    checks = []

    def oracle(fn, *a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)

    for K, N in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
        x = jax.random.normal(next(keys), (KERNEL_BATCH, K)).astype(bf16)
        wf = jax.random.normal(next(keys), (K, N), jnp.float32)
        bn = ops._pick_lane_tile(N, _qmm.DEFAULT_BN)
        bk = ops._pick_lane_tile(K, _qmm.DEFAULT_BK, multiple=32)
        for quant in (quantize_q8_0, quantize_q4_0):
            def run(x=x, w=quant(wf), bn=bn, bk=bk, K=K, N=N):
                got = _qmm.quant_matmul(x, w, bm=KERNEL_BATCH, bn=bn, bk=bk,
                                        out_dtype=jnp.float32)
                want = oracle(ref.quant_matmul_ref, x, w,
                              out_dtype=jnp.float32)
                # test_quant_matmul_allclose, bf16 activations
                return check_close(
                    f"quant_matmul {w.fmt} {K}x{N} tiles {bn}/{bk}", got,
                    want, atol=0.02 * float(jnp.max(jnp.abs(want))),
                    rtol=0.05)
            checks.append(run)

    B, S = KERNEL_BATCH, KERNEL_CACHE
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.random.normal(next(keys), (B, Hq, D)).astype(bf16)
    k = jax.random.normal(next(keys), (B, Hkv, S, D)).astype(bf16)
    v = jax.random.normal(next(keys), (B, Hkv, S, D)).astype(bf16)
    lens = jnp.asarray([S // 2, S, S // 4, 1], jnp.int32)

    def run_bf16():
        bk = ops._pick_tile(S, _da.DEFAULT_BK)
        got = _da.decode_attention(q, k, v, lens, bk=bk)
        want = oracle(ref.decode_attention_ref, q, k, v, kv_len=lens)
        # the bf16 attention tolerance (test_flash_attention_allclose)
        return check_close(f"decode_attention bf16 bk {bk}", got, want,
                           atol=2e-2)
    checks.append(run_bf16)

    for fmt in ("q8_0", "q4_0"):
        def run_quant(fmt=fmt):
            bk = ops._pick_tile(S, _daq.DEFAULT_BK)
            kq, ks = quantize_rows(k, fmt)
            vq, vs = quantize_rows(v, fmt)
            got = _daq.decode_attention_quant(q, kq, ks, vq, vs, lens,
                                              fmt=fmt, bk=bk)
            want = oracle(ref.decode_attention_ref, q,
                          dequantize_rows(kq, ks, fmt),
                          dequantize_rows(vq, vs, fmt), kv_len=lens)
            return check_close(f"decode_attention_quant {fmt} bk {bk}",
                               got, want, atol=2e-2)
        checks.append(run_quant)
    return checks


def phase_kernels():
    failed = 0
    for check in _kernel_checks():
        try:
            say("kernels", "ok " + check())
        except PhaseFailed as e:
            failed += 1
            say("kernels", f"FAIL {e}")
    if failed:
        raise PhaseFailed(f"{failed} kernel check(s) failed")


def _prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=N_REQUESTS)
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32)
            for n in lens]


def _serve(phase, precision, kv_quant, kernels, prompts):
    """Build the engine launch.serve builds, warm it up, then send the
    prompts as greedy requests through the asyncio front-end."""
    args = build_parser().parse_args([
        "--arch", ARCH, "--no-reduced", "--precision", precision,
        "--kv-quant", kv_quant, "--kernels", kernels,
        "--slots", str(SLOTS), "--max-len", str(MAX_LEN)])
    t0 = time.perf_counter()
    engine = build_engine(args)
    jax.block_until_ready(engine.params)
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    fe_warm = AsyncServingFrontend(engine)

    async def warm():
        out = await fe_warm.generate(prompts[0], max_new_tokens=MAX_NEW,
                                     temperature=0.0)
        await fe_warm.close()
        return out
    asyncio.run(warm())
    warm_s = time.perf_counter() - t0
    engine.reset()

    async def drive():
        fe = AsyncServingFrontend(engine)
        outs = await asyncio.gather(
            *(fe.generate(p, max_new_tokens=MAX_NEW, temperature=0.0)
              for p in prompts), return_exceptions=True)
        await fe.close()
        return outs

    t0 = time.perf_counter()
    outs = asyncio.run(drive())
    run_s = time.perf_counter() - t0
    failed = [(i, o) for i, o in enumerate(outs)
              if isinstance(o, BaseException)]
    if failed:
        raise PhaseFailed(f"{len(failed)} request(s) failed: {failed[0][1]!r}")
    bad_len = [i for i, o in enumerate(outs) if len(o) != MAX_NEW]
    if bad_len:
        raise PhaseFailed(f"requests {bad_len} stopped short of {MAX_NEW}")
    st = engine.stats
    if st.poisoned:
        raise PhaseFailed(f"{st.poisoned} request(s) had nonfinite logits")
    say(phase, f"{precision}/{kv_quant} kernels={engine.kernels}: "
               f"{len(outs)} requests done, {st.tokens_generated} tokens, "
               f"{st.megasteps} megasteps (smoke timing, not a benchmark: "
               f"build {build_s:.1f}s, warm-up incl. compile {warm_s:.1f}s,"
               f" run {run_s:.2f}s)")
    return engine, outs


def _first_difference(out, want):
    return next((j for j, (a, b) in enumerate(zip(out, want)) if a != b),
                min(len(out), len(want)))


def phase_serve_xla(prompts, results):
    """Greedy tokens must equal ``reference_decode`` compiled at the
    engine's slot count. The batch-1 oracle is reported beside it: a
    different batch width may round bf16 matmuls differently and flip a
    near-tied greedy token, which is no fault of the engine."""
    engine, outs = _serve("serve-xla", "bf16", "bf16", "xla", prompts)
    results["xla"] = outs
    t0 = time.perf_counter()
    mismatches, batch1 = [], []
    for i, (p, out) in enumerate(zip(prompts, outs)):
        want = engine.model.reference_decode(engine.params, p, MAX_NEW,
                                             max_len=MAX_LEN, batch=SLOTS)
        if out != want:
            mismatches.append(f"request {i} (prompt {len(p)}) differs "
                              f"from token {_first_difference(out, want)}")
        want1 = engine.model.reference_decode(engine.params, p, MAX_NEW,
                                              max_len=MAX_LEN)
        if out != want1:
            batch1.append(f"{i}@{_first_difference(out, want1)}")
    say("serve-xla", f"batch-1 reference_decode agrees on "
                     f"{len(prompts) - len(batch1)}/{len(prompts)} requests"
                     + (f" (request@first differing token: "
                        f"{', '.join(batch1)})" if batch1 else ""))
    if mismatches:
        raise PhaseFailed(f"greedy tokens != reference_decode(batch="
                          f"{SLOTS}): " + "; ".join(mismatches))
    say("serve-xla", f"greedy tokens == reference_decode(batch={SLOTS}) "
                     f"for all {len(outs)} requests (references "
                     f"{time.perf_counter() - t0:.1f}s)")


def phase_serve_pallas(prompts, results):
    xla_outs = results.get("xla")
    for fmt in ("q8_0", "q4_0"):
        engine, outs = _serve("serve-pallas", fmt, fmt, "pallas", prompts)
        hlo = engine._megastep.lower(
            engine.params, engine.cache, engine.state,
            engine._empty_admit(), True).compile().as_text()
        n_kernels = hlo.count("tpu_custom_call")
        if not n_kernels:
            raise PhaseFailed(f"{fmt}: compiled megastep holds no "
                              "tpu_custom_call (kernels fell back to XLA)")
        agree = "n/a (serve-xla produced no tokens)"
        if xla_outs is not None:
            same = sum(a == b for o, x in zip(outs, xla_outs)
                       for a, b in zip(o, x))
            agree = f"{same}/{sum(len(x) for x in xla_outs)} tokens"
        say("serve-pallas", f"{fmt}: megastep HLO has {n_kernels} "
                            f"tpu_custom_call; greedy agreement with "
                            f"serve-xla {agree}")
        del engine
        gc.collect()


def main() -> int:
    try:
        device = phase_device()
    except Exception as e:  # noqa: BLE001 — report, never fall back
        say("device", f"FAIL {e}")
        return 1
    enable_compile_cache()
    cfg = get_config(ARCH)
    prompts = _prompts(cfg.vocab_size)
    ok = True
    results = {}
    phases = (("kernels", phase_kernels),
              ("serve-xla", lambda: phase_serve_xla(prompts, results)),
              ("serve-pallas", lambda: phase_serve_pallas(prompts, results)))
    for name, run in phases:
        try:
            run()
        except PhaseFailed as e:
            ok = False
            say(name, f"FAIL {e}")
        except Exception as e:  # noqa: BLE001 — every phase still runs
            ok = False
            say(name, f"FAIL {type(e).__name__}: {e}")
            traceback.print_exc()
        gc.collect()
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
