"""Host turnaround between megasteps, from the program's spans: the
median, over the window's megasteps, of the time from the end of
``engine.drain_wait`` of megastep N (its block is on the host) to the
end of ``engine.dispatch`` of megastep N + 1 (the next one launched):
the drain, the front end's pass, the hand-off to the engine's thread,
admission staging and the dispatch call.

That path is serial only at pipeline depth 1. At depth 2 or more the
program dispatches N + 1 before it waits for N, so the time has no such
meaning: where any dispatch of N + 1 starts before the drain wait of N
ends, the reading is None."""
import statistics

from perfbench import spans


def read(obs):
    found = spans.of(obs)
    waited = {s.stats["megastep"]: s.end
              for s in found.get("engine.drain_wait", [])
              if "megastep" in s.stats}
    pairs = [(s, waited[s.stats["megastep"] - 1])
             for s in found.get("engine.dispatch", [])
             if s.stats.get("megastep", 0) - 1 in waited]
    if not pairs or any(d.start < w for d, w in pairs):
        return None
    return 1e-6 * statistics.median(d.end - w for d, w in pairs)
