"""Roofline share of the ``quant_matmul`` kernel: the least time its
calls need, over their device time in the traced window. Each call's
shapes are read from its HLO text (activation bf16[M, K], payload
s8[K or K/2, N]: q8_0 or q4_0); its least time is the larger of its
operations over the bf16 peak and its stored weight, scale and
activation bytes over the HBM bandwidth."""
import re

from perfbench import devtrace, work

_SHAPES = re.compile(r"custom-call\(bf16\[(\d+),(\d+)\]\S* %[^,]+, "
                     r"s8\[(\d+),(\d+)\]")


def read(obs):
    if obs.trace is None:
        return None
    evs = obs.trace.matching(devtrace.load_rules()["kernels"]["quant_matmul"])
    if not evs:
        return None
    peaks = work.load_peaks(obs.device_kind)
    group = obs.conf["serving"]["group"]
    least = secs = 0.0
    for e in evs:
        m = _SHAPES.search(e.text)
        M, K, Kq, N = (int(v) for v in m.groups())
        fmt = "q8_0" if Kq == K else "q4_0"
        least += work.least_time(*work.quant_matmul(M, K, N, fmt, group),
                                 peaks)
        secs += (e.end - e.start) * 1e-9
    return 100.0 * least / secs
