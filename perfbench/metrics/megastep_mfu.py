"""Model FLOP utilization of the megastep: the operations the model
needs for the tokens it ran in the window (each token through every
weight matrix and the head, and attention over the live positions),
over the megastep's device time times the chip's bf16 peak."""
from perfbench import devtrace, work
from perfbench.arith import window_steps


def read(obs):
    if obs.trace is None:
        return None
    secs, n = obs.trace.seconds(devtrace.load_rules()["kernels"]["megastep"])
    if not n:
        return None
    m = work.dims(obs.conf)
    steps = window_steps(obs)
    flops = (work.token_flops(obs.conf, 0) * sum(s.advanced for s in steps)
             + m.L * 4.0 * m.Hq * m.hd * sum(s.attended for s in steps))
    peak = work.load_peaks(obs.device_kind)["bf16_flop_per_s"]
    return 100.0 * flops / (secs * peak)
