import os
# 512 placeholder devices BEFORE any jax import (jax locks device count
# on first init). float-normalization-bf16 is disabled because the CPU
# backend legalizes every bf16 dot by converting operands to f32 — a
# CPU-only artifact that doubles the apparent HBM traffic and, worse,
# gets loop-hoisted over scan-over-layers so the whole stacked KV cache
# materializes in f32. TPU executes bf16 dots natively, so disabling
# the pass (we only compile, never run) gives TPU-realistic
# memory/bytes numbers. See EXPERIMENTS.md §Dry-run. The dry-run only
# compiles, on host devices, so it pins the CPU platform: on a machine
# with a TPU it must never take the chip from the process that holds it.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512"
                           " --xla_disable_hlo_passes=float-normalization-bf16")
"""Multi-pod dry-run (deliverable e).

Lowers + compiles the real step functions — train_step for train
shapes, ``prefill`` for prefill shapes, ``decode_step`` (serve_step)
for decode shapes — on the production mesh with ShapeDtypeStruct
stand-ins (no allocation), then records memory_analysis,
cost_analysis, and the collective schedule for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
      --shape train_4k [--multi-pod] [--rules v2] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import dataclasses
import json
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.configs.base import InputShape, ModelConfig
from repro.core import cost_model as cm
from repro.distributed import context as dctx
from repro.distributed.sharding import (
    AxisRules, logical_to_spec, rules_for, tree_shardings)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import Model, input_specs
from repro.models.params import abstract_params, param_pspecs
from repro.training import AdamWConfig, TrainConfig, make_train_step
from repro.training import optimizer as opt_mod


# ---------------------------------------------------------------------------
# Batch sharding specs
# ---------------------------------------------------------------------------

def batch_pspecs(cfg: ModelConfig, shape: InputShape, rules: AxisRules,
                 mesh) -> Dict[str, P]:
    batch_axes = ("batch", "seq")
    if shape.name == "long_500k":
        # batch=1: shard sequence instead (DESIGN.md §5)
        batch_axes = (None, "batch")
    out = {"tokens": logical_to_spec(batch_axes, rules, mesh)}
    if shape.kind == "train":
        out["labels"] = out["tokens"]
    if shape.kind == "decode":
        out = {"tokens": logical_to_spec(("batch", None), rules, mesh)}
    if cfg.arch_type == "audio" and shape.kind in ("train", "prefill"):
        out["frames"] = logical_to_spec(("batch", "seq", None), rules, mesh)
    if cfg.arch_type == "vlm" and shape.kind in ("train", "prefill"):
        out["prefix"] = logical_to_spec(("batch", None, None), rules, mesh)
    return out


def cache_pspecs(model: Model, rules: AxisRules, mesh):
    axes = model.cache_axes()

    def to_spec(a):
        return logical_to_spec(a, rules, mesh)

    return jax.tree_util.tree_map(
        to_spec, axes, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Dry-run one (arch × shape × mesh)
# ---------------------------------------------------------------------------

def _compile_once(cfg: ModelConfig, shape: InputShape, mesh, rules):
    """Lower + compile the step function for (cfg, shape); returns the
    hlo_analysis stats dict."""
    model = Model(cfg)
    with dctx.use_mesh(mesh), dctx.use_rules(rules):
        specs = model.param_specs()
        params_abs = model.abstract_params()
        p_pspecs = param_pspecs(specs, rules, mesh)
        if cfg.quant_policy not in ("bf16", "f16", "f32"):
            from repro.models.params import match_quantized
            p_pspecs = match_quantized(p_pspecs, params_abs)
        p_shardings = tree_shardings(params_abs, p_pspecs, mesh)
        batch_abs = input_specs(cfg, shape.seq_len, shape.global_batch,
                                shape.kind)
        b_pspecs = batch_pspecs(cfg, shape, rules, mesh)
        b_shardings = tree_shardings(batch_abs, b_pspecs, mesh)

        if shape.kind == "train":
            tcfg = TrainConfig(adamw=AdamWConfig())
            step = make_train_step(model, tcfg)
            opt_abs = jax.eval_shape(opt_mod.init_state, params_abs)
            opt_shardings = opt_mod.AdamWState(
                NamedSharding(mesh, P()),
                tree_shardings(opt_abs.mu, p_pspecs, mesh),
                tree_shardings(opt_abs.nu, p_pspecs, mesh))
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, opt_shardings, b_shardings),
                out_shardings=(p_shardings, opt_shardings, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch,
                                         shape.seq_len))
            c_pspecs = cache_pspecs(model, rules, mesh)
            c_shardings = tree_shardings(cache_abs, c_pspecs, mesh)
            jitted = jax.jit(
                model.prefill,
                in_shardings=(p_shardings, b_shardings, c_shardings),
                out_shardings=(None, c_shardings),
                donate_argnums=(2,))
            lowered = jitted.lower(params_abs, batch_abs, cache_abs)
        else:  # decode
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch,
                                         shape.seq_len))
            c_pspecs = cache_pspecs(model, rules, mesh)
            c_shardings = tree_shardings(cache_abs, c_pspecs, mesh)
            jitted = jax.jit(
                model.decode_step,
                in_shardings=(p_shardings, b_shardings["tokens"],
                              c_shardings),
                out_shardings=(None, c_shardings),
                donate_argnums=(2,))
            lowered = jitted.lower(params_abs, batch_abs["tokens"],
                                   cache_abs)

        compiled = lowered.compile()
    return hlo_analysis.analyze_compiled(compiled)


PROBE_TIMEOUT_S = int(os.environ.get("REPRO_PROBE_TIMEOUT", "420"))


def _compile_probe_subprocess(cfg: ModelConfig, shape: InputShape,
                              rules) -> Dict[str, float]:
    """Run one calibration probe in a subprocess with a hard timeout.

    Certain probe configs (unrolled MQA attention over a sharded 32k
    sequence) hit a pathological SPMD partitioner corner and compile for
    >30 min; a subprocess lets us bound that and fall back to the
    analytic graph estimate instead of hanging the sweep.
    """
    import subprocess
    overrides = {f.name: getattr(cfg, f.name)
                 for f in dataclasses.fields(cfg)}
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = ('--xla_force_host_platform_device_count=512"
        " --xla_disable_hlo_passes=float-normalization-bf16')\n"
        "import json, dataclasses\n"
        "from repro.configs.base import ModelConfig, INPUT_SHAPES\n"
        "from repro.launch.dryrun import _compile_once\n"
        "from repro.launch.mesh import make_production_mesh\n"
        "from repro.distributed.sharding import rules_for\n"
        f"cfg = ModelConfig(**json.loads({json.dumps(overrides)!r}))\n"
        f"shape = INPUT_SHAPES[{shape.name!r}]\n"
        "mesh = make_production_mesh()\n"
        f"stats = _compile_once(cfg, shape, mesh, rules_for({rules.name!r}))\n"
        "print('STATS::' + json.dumps({k: stats[k] for k in "
        "('hlo_flops', 'hlo_bytes', 'collective_bytes')}))\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(sys.path)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env,
                          timeout=PROBE_TIMEOUT_S)
    for line in proc.stdout.splitlines():
        if line.startswith("STATS::"):
            return json.loads(line[len("STATS::"):])
    raise RuntimeError(f"probe failed: {proc.stderr[-500:]}")


def _analytic_fallback(cfg: ModelConfig, shape: InputShape,
                       chips: int) -> Dict[str, float]:
    """Graph-model estimate used when calibration probes time out."""
    from repro.core.graph import build_decoder_graph
    seq = 1 if shape.kind == "decode" else shape.seq_len
    kv = shape.seq_len if shape.kind == "decode" else 0
    g = build_decoder_graph(cfg, seq=seq, kv_len=kv,
                            batch=shape.global_batch, fused=True)
    mult = 3.0 if shape.kind == "train" else 1.0
    if shape.kind == "train" and cfg.remat:
        mult = 4.0
    return {"hlo_flops": g.total_flops * mult / chips,
            "hlo_bytes": g.total_bytes * mult / chips,
            "collective_bytes": float("nan"),
            "calibration_fallback": "analytic-graph-model"}


def _calibrated_cost(cfg: ModelConfig, shape: InputShape, mesh, rules
                     ) -> Dict[str, float]:
    """True per-step flops/bytes/collective-bytes.

    XLA cost_analysis counts a while-loop body ONCE, so a scanned
    L-layer stack under-reports by ~L×. We compile small unrolled
    variants (layer stack as a python loop, inner scans unrolled) and
    extrapolate linearly in the layer count; for the hybrid 1:2 pattern
    we probe three depths to price the rglru and attention layers
    separately. attn_block is widened to keep the unrolled HLO small —
    block-granularity mask waste shifts flops by only a few percent.
    """
    # remat=False in the probes: the remat backward under the SPMD
    # partitioner takes 10+ minutes to compile; instead the per-layer
    # FLOP delta is corrected analytically — full per-layer remat adds
    # one forward recompute, i.e. x4/3 over the fwd+bwd cost.
    probe = dict(unroll_scans=True, attn_block=2048, remat=False)
    flop_factor = (4.0 / 3.0 if shape.kind == "train" and cfg.remat
                   else 1.0)

    def cost_at(n_layers: int) -> Dict[str, float]:
        over = dict(probe, num_layers=n_layers)
        if cfg.is_encoder_decoder:
            over["num_encoder_layers"] = n_layers
        c = dataclasses.replace(cfg, **over)
        return _compile_probe_subprocess(c, shape, rules)

    def corrected(k: str, base: float, per_layer_total: float) -> float:
        if k == "hlo_flops":
            return base + flop_factor * per_layer_total
        return base + per_layer_total

    keys = ("hlo_flops", "hlo_bytes", "collective_bytes")
    if cfg.arch_type == "hybrid":
        f1, f2, f3 = cost_at(1), cost_at(2), cost_at(3)
        pattern = cfg.layer_pattern()
        n_rg = sum(k == "rglru" for k in pattern)
        n_at = len(pattern) - n_rg
        out = {}
        for k in keys:
            rg = f2[k] - f1[k]
            at = f3[k] - f2[k]
            base = f1[k] - rg
            out[k] = corrected(k, base, n_rg * rg + n_at * at)
        return out
    f1, f2 = cost_at(1), cost_at(2)
    out = {}
    for k in keys:
        b = f2[k] - f1[k]
        a = f1[k] - b
        out[k] = corrected(k, a, b * cfg.num_layers)
    return out


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            rules_version: str = "v2",
            overrides: Optional[Dict] = None,
            calibrate: bool = True,
            verbose: bool = True) -> Dict:
    cfg = get_config(arch, **(overrides or {}))
    from repro.configs.base import SCHEDULER_VERSIONS
    if rules_version in SCHEDULER_VERSIONS:
        cfg = dataclasses.replace(cfg, scheduler_version=rules_version)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(rules_version)
    chips = mesh.size
    t0 = time.time()

    # 1. compile the FULL production config (scan-over-layers): this is
    #    the lower/compile proof + the memory analysis.
    stats = _compile_once(cfg, shape, mesh, rules)
    t_compile = time.time() - t0

    # 2. calibrated per-step cost (see _calibrated_cost docstring).
    raw = {k: stats[k] for k in ("hlo_flops", "hlo_bytes",
                                 "collective_bytes")}
    if calibrate:
        try:
            cal = _calibrated_cost(cfg, shape, mesh, rules)
            stats.update(cal)
            stats["raw_scan_counts"] = raw
        except Exception as e:  # noqa: BLE001
            stats["calibration_error"] = f"{type(e).__name__}: {e}"
            fb = _analytic_fallback(cfg, shape, mesh.size)
            # keep the (undercounted) scan-measured collectives — the
            # analytic graph has no collective model
            fb["collective_bytes"] = raw["collective_bytes"]
            stats.update(fb)
            stats["raw_scan_counts"] = raw

    n_tokens = (shape.global_batch * shape.seq_len
                if shape.kind == "train" else
                shape.global_batch * (1 if shape.kind == "decode"
                                      else shape.seq_len))
    n_params = cfg.param_count()
    n_active = cfg.param_count(active_only=True)
    # MODEL_FLOPS: 6·N·D for training (fwd+bwd), 2·N·D for inference
    mf = (6.0 if shape.kind == "train" else 2.0) * n_active * n_tokens
    terms = cm.roofline(stats["hlo_flops"], stats["hlo_bytes"],
                        stats["collective_bytes"], chips)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "rules": rules_version,
        "chips": chips,
        "kind": shape.kind,
        "ok": True,
        "compile_s": round(t_compile, 1),
        "total_s": round(time.time() - t0, 1),
        "params": n_params,
        "active_params": n_active,
        "model_flops_per_step_global": mf,
        "model_flops_per_chip": mf / chips,
        "useful_flop_ratio": (mf / chips) / stats["hlo_flops"]
            if stats["hlo_flops"] else 0.0,
        **stats,
        "roofline": terms.as_dict(),
    }
    if verbose:
        print(json.dumps({k: v for k, v in result.items()
                          if k != "collectives_by_kind"}, indent=1,
                         default=str))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rules", default="v2")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip the cost-calibration compiles (multi-pod "
                         "runs only need the compile proof; the roofline "
                         "table is single-pod)")
    args = ap.parse_args()

    results = []
    if args.all:
        combos = [(a, s) for a in ASSIGNED_ARCHS for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]
    # resume: skip combos already in the incremental JSONL
    jsonl = (args.out + "l") if args.out else None
    done = set()
    if jsonl and os.path.exists(jsonl):
        with open(jsonl) as f:
            for line in f:
                r = json.loads(line)
                if r.get("ok"):
                    done.add((r["arch"], r["shape"]))
                    results.append(r)
    for arch, shape in combos:
        if (arch, shape) in done:
            continue
        try:
            r = run_one(arch, shape, multi_pod=args.multi_pod,
                        rules_version=args.rules,
                        calibrate=not args.no_calibrate)
        except Exception as e:  # noqa: BLE001 — report, keep going
            r = {"arch": arch, "shape": shape, "ok": False,
                 "error": f"{type(e).__name__}: {e}"}
            print(f"FAIL {arch} x {shape}: {e}", file=sys.stderr)
        results.append(r)
        if jsonl:
            with open(jsonl, "a") as f:
                f.write(json.dumps(r, default=str) + "\n")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} combos compiled OK")
    if n_ok < len(results):
        sys.exit(1)


if __name__ == "__main__":
    main()
