"""The whole megastep's share of its roofline: the least time the chip
needs for the tokens the engine ran in the window, over the megastep
program's device time there.

The least time is the larger of the model's operations over the bf16
peak (every token through every weight matrix and the head, attention
over the live positions) and the bytes it must read over the HBM
bandwidth: every stored layer and ``lm_head`` weight once on each
substep that runs a token, and each live K and V position once for
every token that attends to it. At one slot, as in the solo cell, the
bytes decide by two orders of magnitude: batch-1 decode is bound by
memory, and this is the share of that bound the step reaches. A
substep counts once a token at most, so at several slots the weights
count at least as often as they must be read."""
from perfbench import devtrace, work
from perfbench.arith import window_steps


def read(obs):
    if obs.trace is None:
        return None
    secs, n = obs.trace.seconds(devtrace.load_rules()["kernels"]["megastep"])
    steps = window_steps(obs)
    if not n or not steps:
        return None
    m = work.dims(obs.conf)
    advanced = sum(s.advanced for s in steps)
    attended = sum(s.attended for s in steps)
    flops = (work.token_flops(obs.conf, 0) * advanced
             + m.L * 4.0 * m.Hq * m.hd * attended)
    w = work.weight_bytes(obs.conf)
    nbytes = ((w["layers"] + w["lm_head"])
              * sum(min(s.substeps, s.advanced) for s in steps)
              + m.L * attended * work.kv_bytes_per_position(obs.conf))
    least = work.least_time(flops, nbytes, work.load_peaks(obs.device_kind))
    return 100.0 * least / secs
