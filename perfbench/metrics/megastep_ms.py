"""Device time of one megastep: the summed device time of the megastep
program's executions in the traced window, over their number."""
from perfbench import devtrace


def read(obs):
    if obs.trace is None:
        return None
    secs, n = obs.trace.seconds(devtrace.load_rules()["kernels"]["megastep"])
    return 1e3 * secs / n if n else None
