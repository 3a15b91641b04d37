"""Run one cell of BENCHMARK.json on the accelerator this starts on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (``setup_s``, from the start of this process): the weights are
made on the device from the seed in one jitted call, the engine is
built and every program the cell's traffic runs is compiled and run
once; then all serving state is dropped. The window then runs the
cell's traffic through the program's asyncio front end for
``--seconds``. With ``--trace 0`` the result holds the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result holds the per-layer metrics, read from the
trace and the harness's spans and counters. After the window, the peak
device memory is read, the program's state is freed and a sample of
the served requests, finished or in flight, is checked against the
configuration's plain float32 reference (``compare.py``).

The last line of standard output is one JSON object. Without an
accelerator, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result; it never falls back to the CPU.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from perfbench import spec as spec_mod  # noqa: E402

TRACE_DIR = ROOT / "perfbench_out" / "trace"


class NoAccelerator(RuntimeError):
    pass


def devices_for(chips: int):
    """The first ``chips`` accelerator devices; never the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator("JAX finds no accelerator (only the CPU)")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX finds "
                            f"{len(devs)}")
    return devs[:chips]


def _compile_counter():
    """Counts backend compilations from the moment it is armed."""
    import jax
    box = {"armed": False, "n": 0}

    def listener(event, duration, **_):
        if box["armed"] and "backend_compile" in event:
            box["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


def run_window(engine, mix: dict, specs, seconds: float, trace: bool,
               compiles):
    """Drive the window; returns (records, start, end, probe, trace
    file or None)."""
    import jax
    from perfbench import window
    from repro.launch.serve import AsyncServingFrontend
    probe = window.StepProbe(engine, trace)
    fe = AsyncServingFrontend(engine)
    state = {}

    def on_start():
        compiles["armed"] = True
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            state["span"] = jax.profiler.TraceAnnotation("bench.window")
            state["span"].__enter__()

    def on_end():
        compiles["armed"] = False
        if trace:
            state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    async def go():
        if mix["loop"] == "open":
            return await window.run_open(fe, specs, seconds,
                                         float(mix.get("lead_in_s", 0.0)),
                                         on_start, on_end)
        return await window.run_closed(
            fe, specs, seconds, int(mix["clients"]), on_start, on_end,
            mix.get("open_at") == "first_token")

    recs, t0, t1 = asyncio.run(go())
    path = None
    if trace:
        from perfbench import devtrace
        path = devtrace.newest_xplane(str(TRACE_DIR))
    return recs, t0, t1, probe, path


def control_config(conf: dict) -> dict:
    """The configuration the program runs as the control of the check:
    ``control.program`` overrides its serving formats (one precision
    down on a path the program has); otherwise the configuration."""
    over = conf.get("control", {}).get("program")
    if not over:
        return conf
    return dict(conf, name=conf["name"] + ".control",
                serving=dict(conf["serving"], **over))


def run_cell(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
             *, require_accelerator: bool = True, compile_cache: bool = True,
             t_start: float = T_PROCESS, limits=None, log=print,
             control: bool = False):
    """Set up, measure and check one cell; returns the result object.
    With ``control``, the configuration's ``control`` takes the
    program's place (``control.py``): the program one precision down,
    or the reference one precision down; the check is unchanged."""
    import jax
    from perfbench import compare, generator, system, weights, work
    devs = devices_for(cell.chips) if require_accelerator else \
        jax.devices()[:1]
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf, mix = cell.config, cell.mix
    served_conf = control_config(conf) if control else conf
    activations = (conf.get("control", {}).get("reference", {})
                   .get("activations") if control else None)
    limits = limits if limits is not None else compare.load_limits(cell.name)
    compiles = _compile_counter()

    def make_weights():
        w = jax.block_until_ready(weights.make(seed, served_conf))
        mem = devs[0].memory_stats() or {}
        log(f"[bench] weights made: device peak {mem.get('peak_bytes_in_use')}"
            f" bytes (engine's cache included), {mem.get('bytes_in_use')} in "
            f"use", file=sys.stderr)
        return w

    cfg = system.model_config(served_conf)
    engine = system.build_engine(cfg, make_weights, served_conf, mix)
    system.warm_up(engine, mix)
    specs = generator.schedule(mix, seed, seconds, work.dims(conf).V)
    setup_s = time.perf_counter() - t_start
    mem = devs[0].memory_stats() or {}
    log(f"[bench] {cell.name} seed {seed}: set-up {setup_s:.1f} s; device "
        f"memory {mem.get('bytes_in_use')} in use, peak "
        f"{mem.get('peak_bytes_in_use')}, limit {mem.get('bytes_limit')}",
        file=sys.stderr)

    recs, t0, t1, probe, trace_path = run_window(engine, mix, specs,
                                                 seconds, trace, compiles)
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    probe.engine = probe._orig = None
    del engine
    gc.collect()

    red = None
    if trace_path is not None:
        from perfbench import devtrace
        red = devtrace.reduce(trace_path)

    gap, sample = compare.logit_gap(conf, mix, seed, recs, activations)
    due = [r for r in recs if r.spec.in_window or mix["loop"] == "closed"]
    failed = sum(r.failed for r in due)
    checks = {
        "logit_gap": {"value": gap, "limit": limits["logit_gap"]["limit"]},
        "failed_requests": {"value": failed, "limit": 0},
        "checked_tokens": {"value": sum(len(r.tokens) for r in sample),
                           "limit": "> 0"},
    }
    correct = (bool(sample) and failed == 0
               and gap <= limits["logit_gap"]["limit"])

    obs = SimpleNamespace(
        conf=conf, mix=mix, setup_s=setup_s, records=recs, t0=t0, t1=t1,
        steps=probe.steps, slots=int(mix["slots"]), trace=red,
        device_kind=devs[0].device_kind)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = spec_mod.read_metrics(entries, obs)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        from perfbench import devtrace
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = devtrace.breakdown(red)
    late = [r.sent - r.due for r in recs if r.sent]
    log(f"[bench] {len(recs)} requests sent, {sum(r.done for r in recs)} "
        f"finished; generator late by {max(late or [0]) * 1e3:.2f} ms at "
        f"most; {compiles['n']} compilations in the window",
        file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec_mod.resolve(spec_mod.load_benchmark(), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        print(f"[bench] {e}: no result", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
