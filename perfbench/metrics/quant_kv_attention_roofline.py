"""Roofline share of the decode-attention kernel over a quantized
(``s8``) KV cache: the least time for the operations and bytes of the
live cache positions it has to read in every layer, over the kernel's
device time in the traced window. The live positions are the program's
own count, the ``attended`` stat of its ``engine.drain`` spans in the
window; a program without that stat reads as nothing. Positions past
a slot's length count for nothing, so a kernel that reads the whole
cache capacity reads as a low share."""
import re

from perfbench import devtrace, spans, work

_S8_CACHE = re.compile(r"custom-call\(s32\[[0-9]+\]\S* %[^,]+, "
                       r"bf16\[[0-9,]+\]\S* %[^,]+, s8\[")


def read(obs):
    attended = spans.stat(obs, "engine.drain", "attended")
    if obs.trace is None or not attended:
        return None
    rule = devtrace.load_rules()["kernels"]["decode_attention"]
    evs = [e for e in obs.trace.matching(rule) if _S8_CACHE.search(e.text)]
    if not evs:
        return None
    secs = sum(e.end - e.start for e in evs) * 1e-9
    m = work.dims(obs.conf)
    flops, nbytes = work.decode_attention(sum(attended), obs.conf)
    least = work.least_time(m.L * flops, m.L * nbytes,
                            work.load_peaks(obs.device_kind))
    return 100.0 * least / secs
