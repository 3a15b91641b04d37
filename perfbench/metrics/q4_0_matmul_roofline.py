"""Roofline share of the ``quant_matmul`` kernel over its q4_0 calls
alone (payload ``s8[K/2, N]`` beside an activation ``bf16[M, K]``):
``quant_matmul_roofline``'s reading with every q8_0 call left out."""
from types import SimpleNamespace

from perfbench import spec

_QMM = spec.reader("quant_matmul_roofline")


def _q4_0(e) -> bool:
    m = _QMM._SHAPES.search(e.text)
    return m is not None and 2 * int(m.group(3)) == int(m.group(2))


class _Q4_0Calls:
    """A trace whose ``quant_matmul`` matches are its q4_0 calls."""

    def __init__(self, trace):
        self._trace = trace

    def matching(self, rule):
        return [e for e in self._trace.matching(rule) if _q4_0(e)]


def read(obs):
    if obs.trace is None:
        return None
    return _QMM.read(SimpleNamespace(**dict(
        vars(obs), trace=_Q4_0Calls(obs.trace))))
