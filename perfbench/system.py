"""The system under test, as the benchmark builds and drives it.

This is the only module of the benchmark that imports the program. It
maps a configuration file onto the program's ``ModelConfig``, hands the
benchmark's seeded weights to the program in its own parameter layout
(pre-quantized, checked against ``Model.abstract_params``), and builds
the ``ServingEngine`` with the arguments ``launch.serve.build_engine``
gives it: the parser's defaults, its sampling, and the cell's formats,
kernels, slots and cache length.
"""
from __future__ import annotations

import asyncio

import jax
import numpy as np

from perfbench import work
from repro.configs import get_config
from repro.launch.serve import AsyncServingFrontend, build_parser
from repro.models import Model
from repro.quant import QuantizedTensor
from repro.serving import SamplingConfig, ServingEngine


def model_config(conf: dict):
    m = work.dims(conf)
    s = conf["serving"]
    cfg = get_config(
        conf["arch"], num_layers=m.L, d_model=m.D, num_heads=m.Hq,
        num_kv_heads=m.Hkv, head_dim=m.hd, d_ff=m.F, vocab_size=m.V,
        norm_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        max_seq_len=int(conf["max_position_embeddings"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        quant_policy=s["weights"], quant_group=int(s["group"]),
        kv_quant=s["kv_cache"], kernels=s["kernels"])
    if cfg.activation != conf["hidden_act"] or not cfg.glu:
        raise ValueError(f"{conf['arch']}: the program runs "
                         f"{cfg.activation} (glu={cfg.glu}), the "
                         f"configuration states {conf['hidden_act']}")
    return cfg


def program_params(w: dict, cfg):
    """The program's parameter tree over the benchmark's weights."""
    def qt(leaf):
        return QuantizedTensor(leaf["q"], leaf["s"], cfg.quant_policy,
                               cfg.quant_group)
    params = {
        "embedding": w["embedding"], "lm_head": qt(w["lm_head"]),
        "final_norm": w["final_norm"],
        "layers": {
            "attn_norm": w["attn_norm"], "ffn_norm": w["ffn_norm"],
            "attn": {"wqkv": {"w": qt(w["wqkv"])}, "wo": {"w": qt(w["wo"])}},
            "mlp": {"w_gate_up": {"w": qt(w["w_gate_up"])},
                    "w_down": {"w": qt(w["w_down"])}}}}
    want = Model(cfg).abstract_params()
    got_def = jax.tree_util.tree_structure(params)
    want_def = jax.tree_util.tree_structure(want)
    if got_def != want_def:
        raise ValueError(f"parameter layout differs from the program's:\n"
                         f"{got_def}\n!=\n{want_def}")
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape} {a.dtype} != program's "
                             f"{b.shape} {b.dtype}")
    return params


def max_len(conf: dict, mix: dict) -> int:
    n = mix["max_len"]
    return int(conf["max_position_embeddings"]) if n == "context" else int(n)


def build_engine(cfg, make_params, conf: dict, mix: dict) -> ServingEngine:
    """The engine over the weights ``make_params()`` returns. The engine
    is built over the weights' shapes first and given the weights after:
    building it allocates the KV cache layer by layer and then stacks
    it, which holds two caches at once, and at 16 slots of deepseek-7b
    that does not fit on one chip beside the weights."""
    a = build_parser().parse_args([])
    engine = ServingEngine(
        Model(cfg), Model(cfg).abstract_params(), slots=int(mix["slots"]),
        max_len=max_len(conf, mix),
        sampling=SamplingConfig(temperature=0.8, top_k=40),
        megastep_k=a.megastep_k, admission=a.admission,
        prefill_chunk=a.prefill_chunk, donate_carries=not a.no_donate,
        kernels=cfg.kernels, pipeline_depth=a.pipeline_depth,
        page_size=a.page_size, prefix_cache=a.prefix_cache,
        max_queue=a.max_queue, quant_policy=cfg.quant_policy,
        kv_quant=cfg.kv_quant)
    engine.params = program_params(make_params(), cfg)
    return engine


def warm_up(engine: ServingEngine, mix: dict) -> None:
    """Compile what the cell's traffic runs: the megastep with a mixed
    batch where the mix samples, and the all-greedy megastep where it
    has greedy requests. A prompt of two chunks and a bit exercises a
    chunk refill. Then drop all state; compiled programs stay."""
    share = float(mix.get("greedy_share", 0.0))
    k = engine.megastep_k
    n = min(2 * engine.prefill_chunk + 3, int(mix["prompt_tokens"]["max"]))
    prompt = (np.arange(n) % 97 + 1).astype(np.int32)
    smp = mix.get("sampling", {})

    async def go():
        fe = AsyncServingFrontend(engine)
        jobs = []
        if share > 0:
            jobs.append(fe.generate(prompt, max_new_tokens=3 * k,
                                    temperature=0.0))
        if share < 1:
            jobs.append(fe.generate(
                prompt, max_new_tokens=k,
                temperature=float(smp.get("temperature", 0.8)),
                top_k=int(smp.get("top_k", 0))))
        await asyncio.gather(*jobs)
        await fe.close()

    asyncio.run(go())
    engine.cache = engine.state = None     # free before the new cache
    engine.reset()
    jax.block_until_ready((engine.cache, engine.state, engine.params))
