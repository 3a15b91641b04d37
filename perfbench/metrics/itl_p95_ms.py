"""95th percentile of the gaps between consecutive streamed tokens of a
request, as the client's ``on_token`` sees them, over every gap that
ends inside the window (host clock)."""
from perfbench.arith import percentile


def read(obs):
    gaps = [b - a for r in obs.records for a, b in zip(r.times, r.times[1:])
            if obs.t0 <= b < obs.t1]
    return 1e3 * percentile(gaps, 95) if gaps else None
