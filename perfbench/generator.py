"""The one traffic generator: turns a mix file and a seed into requests.

A mix (``mixes/<name>.json``) gives the loop (``open``: independent
users on a schedule; ``closed``: ``clients`` users who each send the
next request when the last one completes), the length distributions,
the sampling and, for an open loop, the offered rate.

Every seed draws the same multiset of sizes and gaps, in another order.
Lengths and inter-arrival gaps are the distribution's quantiles at
``(j + 0.5) / n``, visited in a van der Corput order (base 2 for
prompts, 3 for outputs, 5 for gaps) that starts at an offset drawn from
the seed. So a window's work does not change with the seed, and any
stretch of consecutive requests mixes short and long ones. The gaps of
an open loop are exponential quantiles scaled to fill the window
exactly, so the ``round(rate * seconds)`` requests of a window are all
due inside it; its lead-in repeats the window's tail (``schedule``).
Token ids are drawn from the seed.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class RequestSpec:
    index: int
    prompt: np.ndarray            # (len,) int32 token ids in [1, vocab)
    max_new: int
    temperature: float
    top_k: int
    due_s: Optional[float]        # from the window's start; None: closed loop
    in_window: bool               # due inside the measured window

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def radical_inverse(i: int, base: int) -> float:
    out, f = 0.0, 1.0 / base
    while i:
        out += f * (i % base)
        i //= base
        f /= base
    return out


def stratified(n: int, base: int, offset: int) -> np.ndarray:
    """``n`` midpoint quantile levels ``(j + 0.5) / n``, each ``j`` once,
    in the order of the van der Corput sequence from ``offset``."""
    v = [radical_inverse(offset + i, base) for i in range(n)]
    rank = np.empty(n, np.int64)
    rank[np.argsort(v, kind="stable")] = np.arange(n)
    return (rank + 0.5) / n


def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Whole-number lengths at levels ``u`` of a length distribution."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        nd = statistics.NormalDist()
        x = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(p))
             for p in u]
        out = np.rint(x)
    elif dist["dist"] == "uniform":
        out = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def _gaps(n: int, seconds: float, offset: int) -> np.ndarray:
    g = -np.log1p(-stratified(n, 5, offset))
    return g * (seconds / g.sum())


def _block(mix: dict, n: int, rng: np.random.Generator, vocab: int,
           dues, in_window: bool) -> List[RequestSpec]:
    offs = rng.integers(0, 1 << 20, size=2)
    plens = quantile(mix["prompt_tokens"], stratified(n, 2, int(offs[0])))
    outs = quantile(mix["output_tokens"], stratified(n, 3, int(offs[1])))
    share = float(mix.get("greedy_share", 0.0))
    phase = rng.random()
    smp = mix.get("sampling", {})
    reqs = []
    for i in range(n):
        greedy = (math.floor((i + 1) * share + phase)
                  - math.floor(i * share + phase)) >= 1
        reqs.append(RequestSpec(
            index=i,
            prompt=rng.integers(1, vocab, size=int(plens[i])).astype(
                np.int32),
            max_new=int(outs[i]),
            temperature=0.0 if greedy else float(smp.get("temperature", 0)),
            top_k=0 if greedy else int(smp.get("top_k", 0)),
            due_s=None if dues is None else float(dues[i]),
            in_window=in_window))
    return reqs


def schedule(mix: dict, seed: int, seconds: float,
             vocab: int) -> List[RequestSpec]:
    """The requests of one run, in the order they are sent.

    Open loop: the window's ``round(rate * seconds)`` requests, and
    before them a lead-in that repeats the window's last ``lead_in_s``
    seconds of requests (same sizes, sampling and gaps; fresh token
    ids) a window earlier. The window so opens with the work in flight
    that it leaves in flight when it closes, and the tokens it delivers
    are those of its own requests whatever their order. Closed loop:
    ``mix["requests"]`` requests, which the clients take in turn."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "closed":
        return _block(mix, int(mix["requests"]), rng, vocab, None, False)
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    g = _gaps(n, seconds, int(rng.integers(0, 1 << 20)))
    win = _block(mix, n, rng, vocab,
                 np.concatenate([[0.0], np.cumsum(g)[:-1]]), True)
    lead = float(mix.get("lead_in_s", 0.0))
    tail = [r for r in win if r.due_s >= seconds - lead]
    lead_in = [dataclasses.replace(
        r, index=i, due_s=r.due_s - seconds, in_window=False,
        prompt=rng.integers(1, vocab, size=len(r.prompt)).astype(np.int32))
        for i, r in enumerate(tail)]
    for i, r in enumerate(win):
        r.index = len(lead_in) + i
    return lead_in + win
