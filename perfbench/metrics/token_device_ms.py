"""Device time of one substep of the megastep: ``megastep_ms`` (the
megastep program's device time in the traced window, over its
executions) over the substeps a megastep runs, from the engine's
counters. At one slot a substep runs one token, so this is the device
time of a token."""
from perfbench import spec
from perfbench.arith import window_steps


def read(obs):
    per_megastep = spec.reader("megastep_ms").read(obs)
    ran = [s.substeps for s in window_steps(obs) if s.substeps]
    if per_megastep is None or not ran:
        return None
    return per_megastep * len(ran) / sum(ran)
