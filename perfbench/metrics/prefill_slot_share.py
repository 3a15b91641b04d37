"""Share of slot-substeps spent feeding prompt tokens (one token per
substep under chunked admission), over substeps times slots, for the
engine steps of the window."""
from perfbench.arith import window_steps


def read(obs):
    steps = window_steps(obs)
    cap = sum(s.substeps for s in steps) * obs.slots
    return 100.0 * sum(s.fed for s in steps) / cap if cap else None
