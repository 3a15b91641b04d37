"""90th percentile of queue wait: for each request the program placed
in a slot inside the window, the time since it last entered the queue
(``queued_us`` of its ``engine.admit`` span)."""
from perfbench import spans
from perfbench.arith import percentile


def read(obs):
    waits = spans.stat(obs, "engine.admit", "queued_us")
    return 1e-3 * percentile(waits, 90) if waits else None
