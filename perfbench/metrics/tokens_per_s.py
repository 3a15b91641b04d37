"""Output tokens streamed to the clients inside the window, over the
window's seconds (host clock)."""


def read(obs):
    n = sum(obs.t0 <= t < obs.t1 for r in obs.records for t in r.times)
    return n / (obs.t1 - obs.t0)
