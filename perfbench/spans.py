"""The program's own names in a profiler trace.

The serving program opens ``jax.profiler.TraceAnnotation`` host spans
at its layer boundaries (``engine.*``, ``frontend.*``), with numeric
stats as keyword arguments (``megastep``, ``uid``, ``queued_us``, ...).
``of(obs)`` reads the host spans named in ``PROGRAM_SPANS`` from the
run's ``.xplane.pb``, keeps those that start inside the
``bench.window`` span, and groups them by name. The file is
``obs.trace_path`` where the observation carries one, else the newest
trace under ``run.py``'s ``TRACE_DIR``, which a traced run writes
anew. A run with ``--trace 0``, or a program without these spans,
reads as nothing ({}), and the readers built on them leave their
metric out.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from perfbench import devtrace

# the serving program's host spans (``kernels.json``'s ``host_spans``
# holds only the harness's own)
PROGRAM_SPANS = (
    "frontend.step", "frontend.admit", "frontend.cancel", "frontend.publish",
    "engine.submit", "engine.stage", "engine.admit", "engine.dispatch",
    "engine.drain_wait", "engine.drain", "engine.first_token")


@dataclasses.dataclass
class Span:
    start: float     # ns, on the profiler's clock
    end: float       # ns
    stats: dict      # the span's numeric stats


def read(path: str, window_span: str = "bench.window"
         ) -> Dict[str, List[Span]]:
    """The program's host spans that start inside the window span, by
    name, each list in order of start."""
    from jax.profiler import ProfileData
    names = set(PROGRAM_SPANS)
    window = None
    found: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == window_span:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in names:
                    stats = {k: v for k, v in e.stats
                             if isinstance(v, (int, float))}
                    found.setdefault(e.name, []).append(Span(
                        e.start_ns, e.start_ns + e.duration_ns, stats))
    if window is None:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = window
    return {name: sorted((s for s in spans if lo <= s.start < hi),
                         key=lambda s: s.start)
            for name, spans in found.items()}


def trace_file(obs) -> Optional[str]:
    """The run's trace file; None when the run was not traced."""
    path = getattr(obs, "trace_path", None)
    if path is None and getattr(obs, "trace", None) is not None:
        from perfbench.run import TRACE_DIR
        path = devtrace.newest_xplane(str(TRACE_DIR))
    return path


def of(obs) -> Dict[str, List[Span]]:
    """The window's program spans by name, read once per run; {} when
    the run was not traced."""
    path = trace_file(obs)
    if path is None:
        return {}
    if getattr(obs, "program_spans", None) is None:
        obs.program_spans = read(path)
    return obs.program_spans


def stat(obs, name: str, key: str) -> List[float]:
    """The values of stat ``key`` over the window's ``name`` spans."""
    return [s.stats[key] for s in of(obs).get(name, []) if key in s.stats]

