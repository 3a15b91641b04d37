"""Reduce a profiler trace to device busy time, kernel times and gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.
ProfileData``. Device planes are the ``/device:...`` planes that have an op line
(``kernels.json``: ``ops_line``), which holds one event per executed
operation; on a TPU an event's name is the HLO instruction's text,
with its operand and result shapes. Busy time is the union of those events'
intervals inside the traced window, which the harness marks with a
host span named ``bench.window``. Kernels and programs are matched by
the rules of ``kernels.json``: a regular expression searched in the
event's name and the text of its stats.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import pathlib
import re
from typing import Dict, List, Optional, Tuple

RULES_FILE = pathlib.Path(__file__).resolve().parent / "kernels.json"

Interval = Tuple[float, float]


def load_rules() -> dict:
    with open(RULES_FILE) as f:
        return json.load(f)


def union_ns(intervals: List[Interval]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Event:
    name: str
    start: float     # ns
    end: float       # ns
    text: str        # name and stats, for matching


@dataclasses.dataclass
class Reduction:
    window: Interval                      # ns
    busy_ns: float                        # mean over the chips
    ops: List[Event]                      # device ops of the first chip
    lines: Dict[str, List[Event]]         # other device lines, same chip
    host: List[Event]                     # harness spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def matching(self, rule: dict) -> List[Event]:
        evs = (self.ops if rule.get("line", "ops") == "ops"
               else self.lines.get(rule["line"], []))
        pat = re.compile(rule["match"])
        lo, hi = self.window
        hit: Dict[str, bool] = {}       # an op's text repeats every substep
        out = []
        for e in evs:
            if lo <= e.start < hi:
                if e.text not in hit:
                    hit[e.text] = pat.search(e.text) is not None
                if hit[e.text]:
                    out.append(e)
        return out

    def seconds(self, rule: dict) -> Tuple[float, int]:
        evs = self.matching(rule)
        return sum(e.end - e.start for e in evs) * 1e-9, len(evs)


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def _events(line, with_text: bool) -> List[Event]:
    out = []
    for ev in line.events:
        s = float(ev.start_ns)
        out.append(Event(ev.name, s, s + float(ev.duration_ns),
                         _text(ev) if with_text else ev.name))
    return out


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce(path: str, rules: Optional[dict] = None,
           window_span: str = "bench.window") -> Reduction:
    from jax.profiler import ProfileData
    rules = rules or load_rules()
    pd = ProfileData.from_file(path)
    host: List[Event] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and any(
                line.name == rules["ops_line"] for line in plane.lines):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line, False)
                         if e.name in rules["host_spans"]]
    spans = [e for e in host if e.name == window_span]
    if not spans:
        raise ValueError(f"no {window_span!r} span in the trace")
    window = (spans[0].start, spans[0].end)
    busy, ops, lines = [], [], {}
    for i, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        for line in plane.lines:
            if line.name != rules["ops_line"]:
                if i == 0:
                    lines[line.name] = _events(line, True)
                continue
            evs = _events(line, i == 0)
            busy.append(union_ns(clip([(e.start, e.end) for e in evs],
                                      *window)))
            if i == 0:
                ops = evs
    if not busy:
        raise ValueError("the trace holds no device operations")
    return Reduction(window=window, busy_ns=sum(busy) / len(busy), ops=ops,
                     lines=lines, host=host)


# ops that only hold other ops (loops, calls): their time is their body's
_CONTAINER = re.compile(r" (while|conditional|call)\(")
_RESULT = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])")


def label(e: Event, kernels: dict) -> str:
    """A short name for a device op: the kernel rule it matches, if
    any, then its HLO name and result shape."""
    m = _RESULT.match(e.name)
    short = f"{m.group(1)} {m.group(2)}" if m else e.name[:80]
    for k, rule in kernels.items():
        if rule.get("line", "ops") == "ops" and re.search(rule["match"],
                                                          e.text):
            return f"{k} {short}"
    kind = re.search(r"\] ([a-z\-]+)\(", e.name)
    return f"{short} {kind.group(1)}" if kind else short


def breakdown(red: Reduction, n: int = 10) -> dict:
    """The device operations that took most time in the window (loop
    and call ops, which only hold others, left out), and its longest
    idle gaps, each named by the harness span the host was in at the
    gap's midpoint."""
    lo, hi = red.window
    kernels = load_rules()["kernels"]
    labels: Dict[str, Optional[str]] = {}
    by_name: Dict[str, float] = {}
    for e in red.ops:
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if e.text not in labels:
            labels[e.text] = (None if _CONTAINER.search(e.name)
                              else label(e, kernels))
        key = labels[e.text]
        if key is not None:
            by_name[key] = by_name.get(key, 0.0) + (t - s) * 1e-9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    idle = gaps(clip([(e.start, e.end) for e in red.ops], lo, hi), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    inner = [e for e in red.host if e.name != "bench.window"]
    named = []
    for s, t in idle[:n]:
        mid = (s + t) / 2
        where = [e for e in inner if e.start <= mid <= e.end]
        span = (min(where, key=lambda e: e.end - e.start).name if where
                else "outside the harness spans")
        named.append([span, (t - s) * 1e-9])
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": named}
