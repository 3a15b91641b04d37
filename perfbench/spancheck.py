"""Hold the program's host spans to the device trace and to the client.

    python3 perfbench/spancheck.py --workload <cell> --seed <n> \
        --seconds <s>

Runs the cell as ``run.py --trace 1`` does and prints its result line,
then one more JSON line (medians and ranges in ms):

- ``clock``: the share of the window's megasteps whose device execution
  (the megastep program's event on the ``XLA Modules`` line) starts
  after its ``engine.dispatch`` span starts and ends before its
  ``engine.drain_wait`` span ends, with the slack on each side: the
  host spans and the device ops are then on one clock.
- ``gaps``: for consecutive megasteps N, N + 1 run one after the
  other (pipeline depth 1), the host regions from N's device end to
  N + 1's device start: the tail of ``engine.drain_wait`` after the
  device end, ``engine.drain``, the front end (end of the drain to the
  next ``engine.stage``: the front end's pass, of which
  ``frontend_spans`` is its own spans, and the hand-off to the engine's
  thread), ``engine.stage`` (split by whether it ``admitted`` a
  request), ``engine.dispatch``, and dispatch end to device start;
  ``publish_us_per_token`` is ``frontend.publish`` over the tokens it
  streamed (``n``). Pairs where N + 1 was dispatched before N's drain
  wait ended (depth 2 or more) are only counted, as ``overlapped``.
- ``reconcile``: per request of an open loop whose first token
  streamed inside the window, submit → first token from the program's
  spans (``ttft_us``) against due → first ``on_token`` from the client;
  their difference is the generator's lateness, the wait for the front
  end's next pass between engine steps to submit the request, and the
  publish of the token. It splits in two, each part timed from the
  window's start on each side's own clock (so each holds the small
  constant between the clocks, with opposite signs): ``due_to_submit``,
  the client's due time to the program's ``engine.submit``, and
  ``first_token_offset``, the program's ``engine.first_token`` to the
  client's first ``on_token``. Requests are matched to uids by the
  order they were sent (the front end numbers requests in the order
  ``generate`` is called); both parts are flat when the match is
  right.
- ``inside``: ``prefill_slot_share`` and ``slot_busy_share`` counted
  from inside the program, the prompt tokens ``fed`` and the tokens
  ``emitted`` of the window's ``engine.drain`` spans over the same
  slot-substeps as the harness's readings of those names in the result
  line (which the program's counts should match; ``emitted`` also
  counts each request's first token, which the harness counts only
  once it is fed back).
- ``idle_gaps``: the window's ten longest device idle gaps, as the
  result line's ``breakdown`` lists them, each named by the innermost
  program or harness span the host was in at the gap's midpoint (the
  breakdown itself knows only the harness's spans).
- ``spans``: program spans per megastep in the window, and one span's
  cost with the profiler off, timed here.
- ``end_to_end``: the cell's end-to-end metrics read in this traced
  run; less those of an untraced run, they are what tracing costs.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from perfbench import devtrace, spans  # noqa: E402
from perfbench import spec as spec_mod  # noqa: E402


def summary(values):
    """Median and range, in ms, of values in ns."""
    if not values:
        return None
    return {"median": 1e-6 * statistics.median(values),
            "min": 1e-6 * min(values), "max": 1e-6 * max(values),
            "n": len(values)}


def _by_megastep(found, name):
    return {s.stats["megastep"]: s for s in found.get(name, [])
            if "megastep" in s.stats}


def clock(found, execs) -> dict:
    """Pair each dispatch with the device execution that starts nearest
    to it, and check that execution lies between the dispatch's start
    and the end of the same megastep's drain wait."""
    dispatch = _by_megastep(found, "engine.dispatch")
    waits = _by_megastep(found, "engine.drain_wait")
    before, after, inside = [], [], 0
    for n, d in dispatch.items():
        if n not in waits or not execs:
            continue
        e = min(execs, key=lambda e: abs(e.start - d.start))
        before.append(e.start - d.start)
        after.append(waits[n].end - e.end)
        inside += before[-1] >= 0 and after[-1] >= 0
    return {"share": 100.0 * inside / len(before) if before else None,
            "dispatch_start_to_device_start": summary(before),
            "device_end_to_drain_wait_end": summary(after)}


def gaps(found, execs) -> dict:
    dispatch = _by_megastep(found, "engine.dispatch")
    waits = _by_megastep(found, "engine.drain_wait")
    drains = found.get("engine.drain", [])
    stages = found.get("engine.stage", [])
    fe = [s for k in ("frontend.admit", "frontend.cancel",
                      "frontend.publish") for s in found.get(k, [])]
    parts = {k: [] for k in ("drain_wait_tail", "drain", "front_end",
                             "frontend_spans", "stage_admitting",
                             "stage_idle", "dispatch",
                             "dispatch_end_to_device_start",
                             "device_idle")}
    overlapped = 0
    for n, d0 in dispatch.items():
        d1 = dispatch.get(n + 1)
        if d1 is None or n not in waits or not execs:
            continue
        w = waits[n]
        if d1.start < w.end:
            overlapped += 1
            continue
        e0 = min(execs, key=lambda e: abs(e.start - d0.start))
        e1 = min(execs, key=lambda e: abs(e.start - d1.start))
        dr = next((s for s in drains if s.start >= w.end), None)
        st = max((s for s in stages if s.start <= d1.start),
                 key=lambda s: s.start, default=None)
        if dr is None or st is None:
            continue
        parts["drain_wait_tail"].append(w.end - e0.end)
        parts["drain"].append(dr.end - dr.start)
        parts["front_end"].append(st.start - dr.end)
        parts["frontend_spans"].append(sum(
            s.end - s.start for s in fe
            if dr.end <= s.start and s.end <= st.start))
        parts["stage_admitting" if st.stats.get("admitted")
              else "stage_idle"].append(st.end - st.start)
        parts["dispatch"].append(d1.end - d1.start)
        parts["dispatch_end_to_device_start"].append(e1.start - d1.end)
        parts["device_idle"].append(e1.start - e0.end)
    out = {k: summary(v) for k, v in parts.items()}
    pub = [(s.end - s.start, s.stats["n"])
           for s in found.get("frontend.publish", []) if s.stats.get("n")]
    out["publish_us_per_token"] = (
        1e-3 * sum(t for t, _ in pub) / sum(n for _, n in pub)
        if pub else None)
    out["overlapped"] = overlapped
    return out


def reconcile(found, window_start_ns, obs) -> dict:
    firsts = {s.stats["uid"]: s for s in found.get("engine.first_token", [])
              if "uid" in s.stats}
    submits = {s.stats["uid"]: s for s in found.get("engine.submit", [])
               if "uid" in s.stats}
    diff, submit, offset = [], [], []
    for uid, rec in enumerate(obs.records, start=1):
        if uid not in firsts or not rec.times:
            continue
        if not obs.t0 <= rec.times[0] < obs.t1:
            continue
        first = firsts[uid]
        diff.append(1e9 * (rec.times[0] - rec.due)
                    - 1e3 * first.stats["ttft_us"])
        if uid in submits:
            submit.append((submits[uid].start - window_start_ns)
                          - 1e9 * (rec.due - obs.t0))
        offset.append(1e9 * (rec.times[0] - obs.t0)
                      - (first.start - window_start_ns))
    return {"client_minus_program": summary(diff),
            "due_to_submit": summary(submit),
            "first_token_offset": summary(offset)}


def inside(found, obs) -> dict:
    from perfbench.arith import window_steps
    cap = sum(s.substeps for s in window_steps(obs)) * obs.slots
    drains = found.get("engine.drain", [])
    if not cap or not drains:
        return {"prefill_slot_share": None, "slot_busy_share": None}
    fed = sum(s.stats.get("fed", 0) for s in drains)
    emitted = sum(s.stats.get("emitted", 0) for s in drains)
    return {"prefill_slot_share": 100.0 * fed / cap,
            "slot_busy_share": 100.0 * (fed + emitted) / cap}


def idle_gaps(red, found, n: int = 10) -> list:
    """The ``n`` longest idle gaps of the device's op line in the
    window, longest first, each as [span, seconds]: the innermost span,
    of the program's or the harness's, that holds the gap's midpoint."""
    lo, hi = red.window
    idle = devtrace.gaps(devtrace.clip([(e.start, e.end) for e in red.ops],
                                       lo, hi), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    inner = [(e.name, e.start, e.end) for e in red.host
             if e.name != "bench.window"]
    inner += [(k, s.start, s.end) for k, v in found.items() for s in v]
    named = []
    for s, t in idle[:n]:
        mid = (s + t) / 2
        where = [e for e in inner if e[1] <= mid <= e[2]]
        span = (min(where, key=lambda e: e[2] - e[1])[0] if where
                else "outside the spans")
        named.append([span, (t - s) * 1e-9])
    return named


def span_cost_us(n: int = 100000) -> float:
    """Microseconds for one span with two stats, profiler off."""
    import jax
    t = time.perf_counter()
    for i in range(n):
        with jax.profiler.TraceAnnotation("engine.x", uid=i, queued_us=1.5):
            pass
    return 1e6 * (time.perf_counter() - t) / n


def check(obs) -> dict:
    found = spans.of(obs)
    execs = obs.trace.matching(devtrace.load_rules()["kernels"]["megastep"])
    n = len(found.get("engine.dispatch", []))
    per = (sum(len(v) for k, v in found.items() if k != "engine.step") / n
           if n else None)
    cost = span_cost_us()
    return {"clock": clock(found, execs), "gaps": gaps(found, execs),
            "idle_gaps": idle_gaps(obs.trace, found),
            "reconcile": reconcile(found, obs.trace.window[0], obs),
            "inside": inside(found, obs),
            "spans": {"per_megastep": per, "us_each_profiler_off": cost,
                      "ms_per_megastep": (per * cost * 1e-3
                                          if per is not None else None)}}


def main(argv=None) -> int:
    from perfbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec_mod.resolve(spec_mod.load_benchmark(), args.workload)
    seen = {}
    read_metrics = spec_mod.read_metrics

    def keep(entries, obs):
        seen["obs"] = obs
        return read_metrics(entries, obs)
    spec_mod.read_metrics = keep
    try:
        result = run.run_cell(cell, args.seed, args.seconds, True)
    except run.NoAccelerator as e:
        print(f"[spancheck] {e}: no result", file=sys.stderr)
        return 2
    finally:
        spec_mod.read_metrics = read_metrics
    report = check(seen["obs"])
    report["end_to_end"] = spec_mod.read_metrics(cell.end_to_end,
                                                 seen["obs"])
    print(json.dumps(result), flush=True)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
