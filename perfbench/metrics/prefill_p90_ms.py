"""90th percentile of prefill time: for each request whose first token
reached the host inside the window, the time from its placement in a
slot to that token (``prefill_us`` of its ``engine.first_token``
span)."""
from perfbench import spans
from perfbench.arith import percentile


def read(obs):
    times = spans.stat(obs, "engine.first_token", "prefill_us")
    return 1e-3 * percentile(times, 90) if times else None
