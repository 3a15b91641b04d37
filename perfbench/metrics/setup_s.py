"""Seconds from the start of the process to the start of the traffic:
imports, device start-up, weights, engine, compilation or compile-cache
loads, and the warm-up requests. A mix's lead-in, traffic that brings
the engine to its steady state before the window opens, is neither
set-up nor window."""


def read(obs):
    return obs.setup_s
