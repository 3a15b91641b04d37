"""The comparison that decides ``correct``.

After the window, a sample of the requests that streamed tokens,
finished or still in flight when the window closed, drawn from the
seed and holding the longest of them, is replayed through the
configuration's plain reference: each prompt with its served tokens,
teacher-forced, in one float32 forward pass. The number compared is
the widest gap by which a served token's logit lies below the
reference's ``k``-th best logit at that position: ``k`` = 1 for a greedy
request (its best), the request's top-k for a sampled one, which must
draw from that support. Its limit, and the readings it was set from,
are in ``checks/<cell>.json``.

The weights are made again from the seed by the benchmark's own maker,
after the program's state is freed; nothing the program made is read.
For the control of the check (``control.py``), ``activations`` puts the
reference one precision down in the program's place: at each position
of the same prompts and tokens, the token it puts first is read in
place of the served one.
"""
from __future__ import annotations

import importlib.util
import json
from typing import Dict, List, Optional

import numpy as np

from perfbench import spec as spec_mod
from perfbench import weights as wts


def load_limits(cell: str) -> Dict[str, dict]:
    with open(spec_mod.BENCH_DIR / "checks" / f"{cell}.json") as f:
        return json.load(f)["limits"]


def reference_class(conf: dict):
    path = spec_mod.BENCH_DIR / "references" / f"{conf['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        "perfbench_reference_" + conf["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(records, seed: int, max_requests: int) -> List:
    """Requests that streamed tokens and did not fail, finished or in
    flight: the longest, then others in an order drawn from the seed,
    ``max_requests`` at most."""
    served = [r for r in records if r.tokens and not r.failed]
    if not served:
        return []
    served.sort(key=lambda r: (-len(r.tokens), r.spec.index))
    rest = served[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    return [served[0]] + [rest[i] for i in order[:max_requests - 1]]


def support(spec) -> int:
    """The ``k`` a served token must lie within: 1 for greedy, else the
    top-k (0: the whole vocabulary)."""
    return 1 if spec.greedy else int(spec.top_k)


def pack(recs, rows: int, seq_len: int, out_len: int):
    """Rows of prompt + served tokens (the last served token is never
    fed), the positions whose logits predict each served token, the
    served tokens, a mask of the real entries and each row's support."""
    tokens = np.zeros((rows, seq_len), np.int32)
    read_pos = np.zeros((rows, out_len), np.int32)
    served = np.zeros((rows, out_len), np.int32)
    mask = np.zeros((rows, out_len), bool)
    k = np.ones((rows,), np.int32)
    for i, r in enumerate(recs):
        p, out = np.asarray(r.spec.prompt), np.asarray(r.tokens, np.int32)
        seq = np.concatenate([p, out[:-1]])
        tokens[i, :len(seq)] = seq
        n = len(out)
        read_pos[i, :n] = len(p) - 1 + np.arange(n)
        served[i, :n] = out
        mask[i, :n] = True
        k[i] = support(r.spec)
    return tokens, read_pos, served, mask, k


def check_shapes(mix: dict):
    rows = int(mix["check"]["max_requests"])
    out_len = int(mix["output_tokens"]["max"])
    return rows, int(mix["prompt_tokens"]["max"]) + out_len, out_len


def logit_gap(conf: dict, mix: dict, seed: int, records,
              activations: Optional[str] = None):
    """The widest served-token gap over the sample; with
    ``activations``, that of the tokens the reference in that dtype puts
    first. Returns (gap or None, sample)."""
    import jax.numpy as jnp
    recs = sample(records, seed, int(mix["check"]["max_requests"]))
    if not recs:
        return None, recs
    rows, seq_len, out_len = check_shapes(mix)
    tokens, read_pos, served, mask, k = pack(recs, rows, seq_len, out_len)
    mod = reference_class(conf)
    w = wts.make(seed, conf)
    ref = mod.Reference(conf).logits(w, tokens, read_pos)
    if activations:
        ctl = mod.Reference(conf, activations).logits(w, tokens, read_pos)
        served = np.asarray(jnp.argmax(ctl, axis=-1)).astype(np.int32)
        del ctl
    gaps = mod.served_gaps(ref, jnp.asarray(served), jnp.asarray(k),
                           kmax=max(1, int(k.max())))
    gaps = np.asarray(gaps)
    return float(gaps[mask].max()), recs
