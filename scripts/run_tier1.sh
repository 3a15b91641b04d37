#!/usr/bin/env bash
# Tier-1 verification wrapper: the pytest suite with a pinned
# hypothesis seed/profile so runs are deterministic in CI — followed
# by seeded q4_0 weight-quant, q8_0 kv-cache, async front-end and
# paged-serving (prefix-hit admission + cancel-recycle) and chaos
# (pool exhaustion + poisoned logits + recovery under audit) smokes.
#
# By default the *fast* tier runs: pytest.ini excludes tests marked
# `slow` (the cross-arch serving property sweeps that push the full
# suite to ~24 min on this container). Pass --full to clear the
# marker filter and run everything — the pre-merge tier.
#
# With hypothesis installed, tests/_hypothesis_compat.py loads a
# derandomized profile; without it (this container), the compat shim's
# seeded fallback runner draws the identical example stream from
# REPRO_HYP_SEED. REPRO_HYP_EXAMPLES caps examples per property test
# (useful for quick smokes: REPRO_HYP_EXAMPLES=2 scripts/run_tier1.sh).
#
# Usage: scripts/run_tier1.sh [--full] [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export REPRO_HYP_SEED="${REPRO_HYP_SEED:-0}"
export REPRO_PALLAS_INTERPRET="${REPRO_PALLAS_INTERPRET:-1}"

MARKER_ARGS=()
if [[ "${1:-}" == "--full" ]]; then
    shift
    MARKER_ARGS=(-m "")     # clear pytest.ini's "not slow" filter
fi

python -m pytest -x -q "${MARKER_ARGS[@]}" "$@"

echo "[tier1] q4_0 quantized-serving smoke (seeded)"
python - <<'EOF'
import jax, numpy as np
from repro.configs import get_config, reduced
from repro.models import Model
from repro.serving import Request, ServingEngine

cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
              vocab_size=256, num_heads=2, num_kv_heads=1)
m = Model(cfg)
params = m.init(jax.random.PRNGKey(0))
eng = ServingEngine(m, params, slots=2, max_len=64, megastep_k=4,
                    quant_policy="q4_0")
rng = np.random.default_rng(0)
reqs = [Request(uid=i,
                prompt=rng.integers(1, cfg.vocab_size, size=5).astype(np.int32),
                max_new_tokens=6) for i in range(3)]
for r in reqs:
    eng.submit(r)
eng.run()
for r in reqs:
    assert r.done, r.uid
    ref = m.reference_decode(eng.params, r.prompt, r.max_new_tokens)
    assert r.output == ref, (r.uid, r.output, ref)
print(f"[tier1] q4_0 smoke OK: {len(reqs)} requests token-identical "
      f"to the quantized reference")
EOF

echo "[tier1] q8_0 kv-cache serving smoke (seeded)"
python - <<'EOF'
import jax, numpy as np
from repro.configs import get_config, reduced
from repro.models import Model
from repro.serving import Request, ServingEngine

cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
              vocab_size=256, num_heads=2, num_kv_heads=1)
m = Model(cfg)
params = m.init(jax.random.PRNGKey(0))
eng = ServingEngine(m, params, slots=2, max_len=64, megastep_k=4,
                    kv_quant="q8_0")
assert eng.kv_quant == "q8_0"
import jax.numpy as jnp
assert any(l.dtype == jnp.int8
           for l in jax.tree_util.tree_leaves(eng.cache)), \
    "kv_quant engine must hold an int8 cache"
rng = np.random.default_rng(1)
reqs = [Request(uid=i,
                prompt=rng.integers(1, cfg.vocab_size, size=6).astype(np.int32),
                max_new_tokens=6) for i in range(3)]
for r in reqs:
    eng.submit(r)
eng.run()
for r in reqs:
    assert r.done, r.uid
    # same quantized-cache path: the rebound model's reference loop
    ref = eng.model.reference_decode(eng.params, r.prompt,
                                     r.max_new_tokens)
    assert r.output == ref, (r.uid, r.output, ref)
print(f"[tier1] kv-quant smoke OK: {len(reqs)} requests token-identical "
      f"to the quantized-cache reference")
EOF

echo "[tier1] async-serve front-end smoke (deadlines + cancellation)"
python - <<'EOF'
import asyncio
import jax, numpy as np
from repro.configs import get_config, reduced
from repro.models import Model
from repro.serving import Request, ServingEngine
from repro.launch.serve import AsyncServingFrontend, DeadlineExceeded

cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
              vocab_size=256, num_heads=2, num_kv_heads=1)
m = Model(cfg)
params = m.init(jax.random.PRNGKey(0))
eng = ServingEngine(m, params, slots=2, max_len=64, megastep_k=2,
                    pipeline_depth=2)
rng = np.random.default_rng(0)
prompts = [rng.integers(1, cfg.vocab_size, size=5 + i).astype(np.int32)
           for i in range(3)]

async def drive():
    fe = AsyncServingFrontend(eng, max_pending=4)
    streamed = []
    # one request with an impossible deadline: must raise
    # DeadlineExceeded and retire its slot via engine.cancel
    expired = 0
    try:
        await fe.generate(prompts[0], max_new_tokens=500,
                          deadline_s=0.0)
    except DeadlineExceeded:
        expired += 1
    # one explicit task cancellation mid-flight
    victim = asyncio.ensure_future(
        fe.generate(prompts[1], max_new_tokens=500))
    await asyncio.sleep(0.05)
    victim.cancel()
    try:
        await victim
    except asyncio.CancelledError:
        pass
    # a normal request afterwards: streams and completes correctly
    toks = await fe.generate(prompts[2], max_new_tokens=6,
                             deadline_s=30.0,
                             on_token=streamed.append)
    await fe.close()
    return expired, toks, streamed

expired, toks, streamed = asyncio.run(drive())
assert expired == 1, "deadline-expired request did not raise"
assert eng.stats.cancelled >= 2, eng.stats.cancelled
assert toks == streamed == m.reference_decode(params, prompts[2], 6)
assert eng.in_flight == 0 and not eng.has_work()
print(f"[tier1] async-serve smoke OK: 1 deadline expiry + 1 "
      f"cancellation ({eng.stats.cancelled} engine cancels), "
      f"survivor token-identical to reference")
EOF

echo "[tier1] paged-serving smoke (prefix-hit admission + cancel-recycle)"
python - <<'EOF'
import jax, numpy as np
from repro.configs import get_config, reduced
from repro.models import Model
from repro.serving import Request, ServingEngine

cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
              vocab_size=256, num_heads=2, num_kv_heads=1)
m = Model(cfg)
params = m.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(2)
shared = rng.integers(1, cfg.vocab_size, size=17).astype(np.int32)
prompts = [np.concatenate([shared, rng.integers(
               1, cfg.vocab_size, size=3 + i).astype(np.int32)])
           for i in range(5)]

def serve(page, prefix):
    eng = ServingEngine(m, params, slots=2, max_len=64, megastep_k=4,
                        admission="chunked", prefill_chunk=16,
                        page_size=page, prefix_cache=prefix)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, [r.output for r in reqs]

dense_eng, dense_out = serve(0, False)
eng, paged_out = serve(8, True)
assert paged_out == dense_out, "paged+prefix tokens diverged from dense"
hits = eng.stats.prefix_hits
assert hits >= 1, "no prefix-hit admission occurred"
assert eng.stats.prefix_hit_tokens >= 8, eng.stats.prefix_hit_tokens
# only the registry's own references survive the drain
assert eng.blocks_in_use == len(eng._prefix_reg) > 0, \
    (eng.blocks_in_use, len(eng._prefix_reg))

# cancel-recycle: retire a mid-decode slot and confirm its private
# blocks return to the free list while shared prefix pages stay live
eng.reset()
a = Request(uid=10, prompt=prompts[0], max_new_tokens=24)
b = Request(uid=11, prompt=prompts[1], max_new_tokens=6)
eng.submit(a)
eng.submit(b)
while not a.output and not a.done:
    eng.step()
live_mid = eng.blocks_in_use
assert eng.cancel(a)
assert eng.blocks_in_use < live_mid, "cancel freed no blocks"
eng.run()
assert b.output == dense_out[1], "cancel corrupted the neighbour slot"
assert eng.blocks_in_use == len(eng._prefix_reg), \
    "cancel leaked (or over-freed) cache blocks"
print(f"[tier1] paged smoke OK: 5 requests token-identical to dense, "
      f"{hits} prefix hit(s), cancel recycled blocks "
      f"({eng.blocks_in_use} registry-held blocks live after drain)")
EOF

echo "[tier1] chaos smoke (pool exhaustion + poisoned logits + recovery)"
python - <<'EOF'
import jax, numpy as np
from repro.configs import get_config, reduced
from repro.models import Model
from repro.serving import (FaultEvent, FaultInjector, FaultSchedule,
                           Request, ServingEngine)

cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
              vocab_size=256, num_heads=2, num_kv_heads=1)
m = Model(cfg)
params = m.init(jax.random.PRNGKey(0))
# 12 usable blocks fully back the 3 slots; contention comes from the
# exhaust_pool fault quarantining most of the pool mid-flight
eng = ServingEngine(m, params, slots=3, max_len=64, megastep_k=4,
                    admission="chunked", prefill_chunk=16,
                    page_size=8, cache_blocks=13)
rng = np.random.default_rng(3)
reqs = [Request(uid=i, prompt=rng.integers(
            1, cfg.vocab_size, size=int(rng.integers(4, 14))
        ).astype(np.int32), max_new_tokens=8) for i in range(5)]
for r in reqs:
    eng.submit(r)
sched = FaultSchedule([
    FaultEvent(0, "poison_logits", ridx=2),     # sticks to uid 2
    FaultEvent(1, "exhaust_pool", blocks=9, duration=2),
    FaultEvent(4, "preempt", ridx=0),
])
inj = FaultInjector(eng, sched, audit=True, sleep=lambda s: None)
inj.run(reqs)                    # audits after every step
assert not eng.has_work() and not eng._quarantined
assert eng.blocks_in_use == len(eng._prefix_reg), "blocks leaked"
assert reqs[2].error == "nonfinite-logits", reqs[2].error
assert eng.stats.poisoned == 1 and eng.stats.preemptions >= 1
for r in reqs:
    assert r.done, r.uid
    ref = m.reference_decode(params, r.prompt, r.max_new_tokens)
    if r.error is None:
        assert r.output == ref, (r.uid, r.output, ref)
    else:                        # pre-poison tokens: clean ref prefix
        assert r.output == ref[:len(r.output)], r.uid
print(f"[tier1] chaos smoke OK: pool exhausted+recovered, 1 poisoned "
      f"retire, {eng.stats.preemptions} preemption(s), survivors "
      f"token-identical, audit held for {inj.steps_run} steps")
EOF
