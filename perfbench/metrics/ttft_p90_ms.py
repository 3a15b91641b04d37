"""90th percentile of time to first token over every request due in the
window, from its due time (host clock). A request with no first token
by the window's end, or one that failed, enters at (window end - due
time). Only an open loop has due times."""
from perfbench.arith import percentile


def read(obs):
    if obs.mix["loop"] != "open":
        return None
    vals = []
    for r in obs.records:
        if not r.spec.in_window:
            continue
        first = r.times[0] if r.times and not r.failed else obs.t1
        vals.append(min(first, obs.t1) - r.due)
    return 1e3 * percentile(vals, 90) if vals else None
