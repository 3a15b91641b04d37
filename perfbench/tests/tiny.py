"""A cell of the benchmark shrunk to run on the CPU in seconds: the
real configuration files and mixes with their sizes cut, for tests of
the harness. No number from it is a measurement."""
from __future__ import annotations

import copy
import json

from perfbench import spec

LIMIT = 0.1         # widest logit gap a sound tiny run may show


def config(name: str = "deepseek-7b.q8_0") -> dict:
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    conf.update(name=f"tiny.{name}", hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_hidden_layers=2, vocab_size=512,
                max_position_embeddings=128)
    return conf


def mix(name: str) -> dict:
    m = copy.deepcopy(spec.load_mix(name))
    if m["loop"] == "open":
        m.update(rate_per_s=5.0, lead_in_s=0.5, slots=4, max_len=96)
        m["prompt_tokens"].update(median=10, min=4, max=24)
        m["output_tokens"].update(median=8, min=4, max=16)
        m["greedy_share"] = 0.5
    else:
        m["prompt_tokens"].update(min=4, max=12)
        m["output_tokens"].update(min=8, max=24)
        m["check"]["max_requests"] = 6
    return m


def cell(config_name: str = "deepseek-7b.q8_0", mix_name: str = "chat",
         **mix_over) -> spec.Cell:
    bench = spec.load_benchmark()
    return spec.Cell(name="tiny", chips=1, config=config(config_name),
                     mix=dict(mix(mix_name), **mix_over),
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def run(c: spec.Cell, seed: int = 7, seconds: float = 2.0, **kw) -> dict:
    """One run of ``run.run_cell`` on the CPU, without the chip check."""
    import time
    from perfbench import run as run_mod
    return run_mod.run_cell(c, seed, seconds, False,
                            require_accelerator=False, compile_cache=False,
                            t_start=time.perf_counter(),
                            limits={"logit_gap": {"limit": LIMIT}},
                            log=lambda *a, **k: None, **kw)
