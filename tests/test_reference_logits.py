"""The dense serving path against an independent float32 reference.

Greedy requests are served through ``ServingEngine`` over the
benchmark's seeded weights (``perfbench.weights.make``, mapped into the
program by ``perfbench.system``), and each served token is held to the
float32 reference of the same llama architecture
(``perfbench/references/llama.py``), which shares no code with the
program. The number compared is the widest gap by which a served
token's reference logit lies below the reference's best logit at its
position; it must stay within ``tiny.LIMIT`` (0.1), the limit the
benchmark's tiny CPU runs use. A greedy token the program picks from a
near tie lies within that limit; a wrong projection, head grouping,
rotary embedding or cache layout lies far outside it.

The widths are ``perfbench/tests/tiny.py``'s (hidden 64, FFN 128, two
layers, vocabulary 512, four query heads of 16) over every weight format
and cache format the benchmark serves, both kernel backends, and MHA,
GQA and MQA. A q4_0 cache is not in the grid: it disagrees with the
reference today (ROADMAP, the q4_0-cache debt).
"""
import numpy as np
import pytest

from perfbench import system, weights
from perfbench.references import llama
from perfbench.tests import tiny
from repro.models import Model
from repro.serving import Request, ServingEngine

SEED = 11
PROMPTS = ((5, 17, 3, 250, 9, 41, 2), (1, 2, 3), tuple(range(30, 50)))
MAX_NEW = 8


def _conf(weights_fmt: str, kv_cache: str, kernels: str, kv_heads: int):
    conf = tiny.config()
    # weights.make caches its jitted maker by name: one name a case
    conf["name"] = (f"reference-logits.{weights_fmt}.{kv_cache}.{kernels}"
                    f".kv{kv_heads}")
    conf["num_key_value_heads"] = kv_heads
    conf["serving"] = dict(conf["serving"], weights=weights_fmt,
                           kv_cache=kv_cache, kernels=kernels)
    return conf


def _serve(cfg, params):
    engine = ServingEngine(Model(cfg), params, slots=4, max_len=64,
                           kernels=cfg.kernels,
                           quant_policy=cfg.quant_policy,
                           kv_quant=cfg.kv_quant)
    reqs = [Request(uid=i, prompt=np.asarray(p, np.int32),
                    max_new_tokens=MAX_NEW) for i, p in enumerate(PROMPTS)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done and r.error is None for r in reqs)
    return [r.output for r in reqs]


def _widest_gap(conf, w, outputs) -> float:
    """Teacher-force each prompt with its served tokens through the
    reference; the widest gap of a served token below the best logit."""
    seq_len = max(len(p) for p in PROMPTS) + MAX_NEW
    tokens = np.zeros((len(PROMPTS), seq_len), np.int32)
    read_pos = np.zeros((len(PROMPTS), MAX_NEW), np.int32)
    served = np.zeros((len(PROMPTS), MAX_NEW), np.int32)
    for i, (p, out) in enumerate(zip(PROMPTS, outputs)):
        assert len(out) == MAX_NEW
        seq = list(p) + list(out[:-1])
        tokens[i, :len(seq)] = seq
        read_pos[i] = len(p) - 1 + np.arange(MAX_NEW)
        served[i] = out
    ref = llama.Reference(conf).logits(w, tokens, read_pos)
    gaps = llama.served_gaps(ref, served, np.ones(len(PROMPTS), np.int32),
                             kmax=1)
    return float(np.max(gaps))


@pytest.mark.parametrize("kv_heads", [4, 2, 1], ids=["mha", "gqa", "mqa"])
@pytest.mark.parametrize("kernels", ["xla", "pallas"])
@pytest.mark.parametrize("kv_cache", ["bf16", "q8_0"])
@pytest.mark.parametrize("weights_fmt", ["q8_0", "q4_0"])
def test_served_tokens_match_float32_reference(weights_fmt, kv_cache,
                                               kernels, kv_heads):
    conf = _conf(weights_fmt, kv_cache, kernels, kv_heads)
    cfg = system.model_config(conf)
    assert (cfg.quant_policy, cfg.kv_quant, cfg.kernels,
            cfg.num_kv_heads) == (weights_fmt, kv_cache, kernels, kv_heads)
    w = weights.make(SEED, conf)
    outputs = _serve(cfg, system.program_params(w, cfg))
    gap = _widest_gap(conf, w, outputs)
    assert gap <= tiny.LIMIT, (gap, outputs)
