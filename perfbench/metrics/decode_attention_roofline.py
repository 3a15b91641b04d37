"""Roofline share of the decode-attention kernel (bf16 or quantized
cache): the least time for the operations and bytes of the live cache
positions it has to read, in every layer, over the kernel's device
time in the traced window. Positions past a slot's length count for
nothing, so a kernel that reads the whole cache reads as a low share."""
from perfbench import devtrace, work
from perfbench.arith import window_steps


def read(obs):
    if obs.trace is None:
        return None
    secs, n = obs.trace.seconds(
        devtrace.load_rules()["kernels"]["decode_attention"])
    if not n:
        return None
    m = work.dims(obs.conf)
    live = sum(s.attended for s in window_steps(obs))
    flops, nbytes = work.decode_attention(live, obs.conf)
    least = work.least_time(m.L * flops, m.L * nbytes,
                            work.load_peaks(obs.device_kind))
    return 100.0 * least / secs
