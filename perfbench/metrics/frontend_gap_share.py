"""Share of the window in which the front end holds the engine idle:
host time between the end of one ``engine.step`` call and the start of
the next while the engine still has work, from the harness's spans
around the calls (host clock)."""
from perfbench.arith import window_steps


def read(obs):
    steps = window_steps(obs)
    gap = sum(max(min(b.start, obs.t1) - a.end, 0.0)
              for a, b in zip(steps, steps[1:]) if a.pending)
    return 100.0 * gap / (obs.t1 - obs.t0) if len(steps) > 1 else None
