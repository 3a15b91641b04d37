"""Find the highest rate an open-loop cell sustains, by a sweep.

    python3 perfbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.2,0.3,0.4

One process: the weights, the engine and its warm-up are made once;
then the cell's window runs once for each offered rate, from a fresh
engine state. For each rate it prints one JSON line: tokens/s, time to
first token (median and 90th percentile), the median time to first
token of the first and last third of the window's requests, and how
many requests due in the window had no first token when it closed. A
rate is sustained while the last third waits no longer than the first
and the backlog stays under the slot count. The mix file then gets
about four fifths of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from perfbench import generator, spec, system, weights, work  # noqa: E402
from perfbench import run as run_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    run_mod.devices_for(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf = cell.config
    cfg = system.model_config(conf)
    engine = system.build_engine(
        cfg, lambda: weights.make(args.seed, conf), conf, cell.mix)
    system.warm_up(engine, cell.mix)
    compiles = run_mod._compile_counter()
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        specs = generator.schedule(mix, args.seed, args.seconds,
                                   work.dims(conf).V)
        recs, t0, t1, probe, _ = run_mod.run_window(
            engine, mix, specs, args.seconds, False, compiles)
        engine.step = probe._orig
        due = sorted((r for r in recs if r.spec.in_window),
                     key=lambda r: r.due)
        ttft = [min(r.times[0] if r.times else t1, t1) - r.due for r in due]
        third = max(len(due) // 3, 1)
        steps = [s for s in probe.steps if t0 <= s.start < t1]
        sub = sum(s.substeps for s in steps)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "tokens_per_s": sum(t0 <= t < t1 for r in recs
                                for t in r.times) / (t1 - t0),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p90_s": float(np.percentile(ttft, 90)),
            "ttft_first_third_s": float(np.median(ttft[:third])),
            "ttft_last_third_s": float(np.median(ttft[-third:])),
            "no_first_token": sum(not r.times or r.times[0] > t1
                                  for r in due),
            "substep_ms": (1e3 * sum(s.end - s.start for s in steps) / sub
                           if sub else None),
            "slot_busy": (sum(s.advanced for s in steps)
                          / (sub * engine.slots) if sub else None),
            "compiles": compiles["n"]}), flush=True)
        engine.cache = engine.state = None
        engine.reset()
    return 0


if __name__ == "__main__":
    sys.exit(main())
