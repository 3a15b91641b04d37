"""Share of slot-substeps that ran a token through the model (a prompt
token fed or an output fed back), over substeps times slots, for the
engine steps of the window (engine counters and its host view of the
slots)."""
from perfbench.arith import window_steps


def read(obs):
    steps = window_steps(obs)
    cap = sum(s.substeps for s in steps) * obs.slots
    work = sum(s.advanced for s in steps)
    return 100.0 * work / cap if cap else None
