"""Overload-safe serving: typed admission rejects, EDF queue ordering,
block-pool preemption/resume, poisoned-slot isolation, the allocator
audit, and the fault injector's retry machinery.

Chaos *sweeps* (seeded schedules x engine dimensions) live in
test_chaos_properties.py; this file pins each mechanism individually
with hand-built orderings — including the two cancel-vs-preemption
interleavings that used to double-free blocks.
"""
import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import Model
from repro.serving import (EngineAuditError, FaultEvent, FaultInjector,
                           FaultSchedule, InfeasibleDeadline,
                           PromptTooLong, QueueFull, Request,
                           ServingEngine, SubmitReject,
                           TransientStepFault)

_STATE = {}


def _model():
    if "m" not in _STATE:
        cfg = reduced(get_config("deepseek-7b"), d_model=64, d_ff=128,
                      vocab_size=256, num_heads=2, num_kv_heads=1)
        m = Model(cfg)
        _STATE["m"] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _STATE["m"]


def _engine(key, **kw):
    if key not in _STATE:
        cfg, m, params = _model()
        _STATE[key] = ServingEngine(m, params, **kw)
    eng = _STATE[key]
    eng.reset()
    return eng


def _req(uid, n_prompt=6, max_new=6, **kw):
    return Request(uid=uid,
                   prompt=(np.arange(n_prompt, dtype=np.int32) % 200)
                   + 1 + uid,
                   max_new_tokens=max_new, **kw)


# -- typed rejects ---------------------------------------------------------

def test_submit_rejects_oversized_prompt_dense():
    """A prompt longer than the dense full-attention cache used to be
    accepted and corrupt the slot's rows at prefill — now a typed
    reject at admission."""
    eng = _engine("dense2", slots=2, max_len=32)
    with pytest.raises(PromptTooLong) as ei:
        eng.submit(_req(0, n_prompt=40))
    assert isinstance(ei.value, SubmitReject)
    assert isinstance(ei.value, ValueError)     # old catch sites hold
    assert ei.value.reason == "prompt_too_long"
    # prompt == capacity is legal; max_new past capacity rings legally
    eng.submit(_req(1, n_prompt=32, max_new=4))
    assert len(eng.queue) == 1


def test_submit_rejects_prompt_exceeding_paged_pool():
    eng = _engine("paged_tiny", slots=2, max_len=64, page_size=8,
                  cache_blocks=5)
    with pytest.raises(PromptTooLong):
        # needs ceil((30+10)/8)=5 pages > 4 usable: can never admit
        eng.submit(_req(0, n_prompt=30, max_new=10))


def test_submit_oversized_prompt_ok_for_recurrent():
    """SSM state is O(1) in sequence length — long prompts are legal
    there and must not be shed."""
    cfg = reduced(get_config("mamba2-2.7b"))
    m = Model(cfg)
    eng = ServingEngine(m, m.init(jax.random.PRNGKey(0)), slots=1,
                        max_len=16)
    eng.submit(Request(uid=0, prompt=np.ones(40, np.int32),
                       max_new_tokens=2))
    assert len(eng.queue) == 1


def test_queue_full_sheds_with_metadata():
    eng = _engine("bounded", slots=1, max_len=32, max_queue=2)
    eng.submit(_req(0))
    eng.submit(_req(1))
    with pytest.raises(QueueFull) as ei:
        eng.submit(_req(2))
    assert ei.value.reason == "queue_full"
    assert ei.value.queue_depth == 2
    assert ei.value.retry_after_s is None      # nothing measured yet
    assert eng.stats.shed == 1
    # drain, then the hint comes from the measured substep rate
    eng.run()
    eng.submit(_req(3))
    eng.submit(_req(4))
    with pytest.raises(QueueFull) as ei:
        eng.submit(_req(5))
    assert ei.value.retry_after_s is not None
    assert ei.value.retry_after_s > 0.0


def test_infeasible_deadline_sheds():
    eng = _engine("bounded", slots=1, max_len=32, max_queue=2)
    with pytest.raises(InfeasibleDeadline) as ei:
        eng.submit(_req(0, deadline_s=0.0))
    assert ei.value.reason == "infeasible_deadline"
    assert eng.stats.shed == 1
    # a generous deadline admits (and completes) fine
    r = _req(1, deadline_s=60.0)
    eng.submit(r)
    eng.run()
    assert r.done and len(r.output) == 6


def test_edf_queue_ordering():
    """Deadline requests drain earliest-first; deadline-less ones stay
    FIFO behind every deadline."""
    eng = _engine("dense2", slots=2, max_len=32)
    r_fifo = _req(0)
    r_late = _req(1, deadline_s=60.0)
    r_soon = _req(2, deadline_s=5.0)
    for r in (r_fifo, r_late, r_soon):
        eng.submit(r)
    assert [r.uid for r in eng.queue] == [2, 1, 0]


# -- preemption / resume ---------------------------------------------------

def test_preempt_resume_token_identical():
    cfg, m, params = _model()
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    r = _req(0, n_prompt=8, max_new=8)
    ref = m.reference_decode(params, r.prompt, 8)
    eng.submit(r)
    eng.step()
    eng.step()                      # some decode progress
    assert eng.preempt(r)
    assert r.preemptions == 1
    assert any(q is r for q in eng.queue)
    eng.audit()
    eng.run()
    eng.audit()
    assert r.done and r.output == ref
    assert eng.stats.preemptions == 1
    assert eng.blocks_in_use == 0   # nothing leaked


def test_pool_starved_admission_preempts_later_deadline_victim():
    """An EDF-earlier arrival evicts a later-deadline occupant when the
    pool can't back both; the victim resumes token-identical."""
    cfg, m, params = _model()
    # 8 usable blocks; each request needs ceil((8+8)/8)=2 pages; keep
    # 6 quarantined so only one request fits at a time
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    victim = _req(0, n_prompt=8, max_new=8, deadline_s=120.0)
    urgent = _req(1, n_prompt=8, max_new=8, deadline_s=30.0)
    refs = {r.uid: m.reference_decode(params, r.prompt, 8)
            for r in (victim, urgent)}
    eng.submit(victim)
    eng.step()                      # victim occupies the pool
    assert eng.quarantine_blocks(6) == 6
    eng.submit(urgent)
    eng.step()                      # urgent's admission must preempt
    eng.audit()
    assert victim.preemptions == 1
    eng.release_quarantined()
    eng.run()
    eng.audit()
    for r in (victim, urgent):
        assert r.done and r.error is None
        assert r.output == refs[r.uid], r.uid
    assert eng.blocks_in_use == 0


def test_fifo_overload_blocks_instead_of_preempting():
    """Same-class (deadline-less) traffic must never preempt — the
    EDF-key guard: a queued arrival is younger than every active
    request, so pool exhaustion blocks FIFO instead of thrashing."""
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    eng.quarantine_blocks(6)
    a, b = _req(0, n_prompt=8, max_new=8), _req(1, n_prompt=8, max_new=8)
    eng.submit(a)
    eng.submit(b)
    eng.step()
    assert a.preemptions == 0 and b.preemptions == 0
    assert any(q is b for q in eng.queue)     # blocked, not preempting
    eng.release_quarantined()
    eng.run()
    assert a.done and b.done and eng.stats.preemptions == 0


# -- cancel x preemption orderings (the double-free regression) ------------

def test_cancel_after_preempt_is_clean_noop():
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    r = _req(0, n_prompt=8, max_new=8)
    eng.submit(r)
    eng.step()
    assert eng.preempt(r)           # blocks recycled, requeued
    eng.audit()
    used = eng.blocks_in_use
    assert eng.cancel(r) is True    # queue path — must not re-release
    eng.audit()
    assert eng.blocks_in_use == used
    assert r.cancelled and not any(q is r for q in eng.queue)
    eng.run()
    eng.audit()


def test_preempt_after_cancel_refuses():
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    r = _req(0, n_prompt=8, max_new=8)
    eng.submit(r)
    eng.step()
    assert eng.cancel(r) is True
    eng.audit()
    assert eng.preempt(r) is False  # no slot, no double-release
    eng.audit()
    assert eng.stats.preemptions == 0
    eng.run()


# -- poisoned-request isolation --------------------------------------------

def test_poisoned_request_isolated_from_batch():
    cfg, m, params = _model()
    eng = _engine("dense2k4", slots=2, max_len=64, megastep_k=4)
    good, bad = _req(0, n_prompt=6, max_new=8), _req(7, n_prompt=5,
                                                     max_new=8)
    ref = m.reference_decode(params, good.prompt, 8)
    eng.submit(good)
    eng.submit(bad)
    eng.inject_logit_poison(bad)
    eng.run()
    assert bad.done and bad.error == "nonfinite-logits"
    assert eng.stats.poisoned == 1
    # the co-batched survivor is byte-identical to a clean run
    assert good.done and good.error is None and good.output == ref
    # and the engine serves the next wave normally
    nxt = _req(20, n_prompt=6, max_new=8)
    eng.submit(nxt)
    eng.run()
    assert nxt.output == m.reference_decode(params, nxt.prompt, 8)


def test_poison_mid_stream_keeps_clean_prefix():
    cfg, m, params = _model()
    eng = _engine("dense2k4", slots=2, max_len=64, megastep_k=4)
    r = _req(0, n_prompt=6, max_new=12)
    ref = m.reference_decode(params, r.prompt, 12)
    eng.submit(r)
    eng.step()                      # emits some clean tokens first
    eng.inject_logit_poison(r)
    eng.run()
    assert r.error == "nonfinite-logits"
    assert len(r.output) < 12
    assert r.output == ref[:len(r.output)]


# -- audit + quarantine ----------------------------------------------------

def test_audit_catches_refcount_corruption():
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    r = _req(0, n_prompt=8, max_new=8)
    eng.submit(r)
    eng.step()
    eng.audit()
    blk = eng._slot_blocks[0][0]
    eng._ref[blk] += 1              # simulate a leaked reference
    with pytest.raises(EngineAuditError):
        eng.audit()
    eng._ref[blk] -= 1
    eng.audit()
    eng.run()


def test_audit_catches_double_ownership():
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    r = _req(0, n_prompt=8, max_new=8)
    eng.submit(r)
    eng.step()
    blk = eng._slot_blocks[0][0]
    eng._free.append(blk)           # referenced AND free
    with pytest.raises(EngineAuditError):
        eng.audit()
    eng._free.remove(blk)
    eng.audit()
    eng.run()


def test_quarantine_is_audited_owner_class():
    eng = _engine("paged9", slots=2, max_len=64, page_size=8,
                  cache_blocks=9, megastep_k=4)
    took = eng.quarantine_blocks(3)
    assert took == 3
    eng.audit()                     # partition holds mid-quarantine
    assert eng.release_quarantined(1) == 1
    eng.audit()
    assert eng.release_quarantined() == 2
    eng.audit()
    assert len(eng._free) == 8


# -- fault injector --------------------------------------------------------

def test_transient_fault_retries_and_recovers():
    cfg, m, params = _model()
    eng = _engine("dense2k4", slots=2, max_len=64, megastep_k=4)
    r = _req(0, n_prompt=6, max_new=8)
    ref = m.reference_decode(params, r.prompt, 8)
    eng.submit(r)
    naps = []
    inj = FaultInjector(
        eng, FaultSchedule([FaultEvent(0, "step_exception", fires=2)]),
        max_retries=3, backoff_s=0.001, sleep=naps.append)
    inj.run([r])
    assert r.done and r.output == ref
    assert inj.retries == 2
    assert naps == [0.001, 0.002]   # exponential backoff, bounded


def test_transient_fault_exhausts_retries():
    eng = _engine("dense2k4", slots=2, max_len=64, megastep_k=4)
    r = _req(0, n_prompt=6, max_new=8)
    eng.submit(r)
    inj = FaultInjector(
        eng, FaultSchedule([FaultEvent(0, "step_exception", fires=9)]),
        max_retries=2, backoff_s=0.0, sleep=lambda s: None)
    with pytest.raises(TransientStepFault):
        inj.run([r])
    assert inj.retries == 2
    eng.reset()


def test_seeded_schedule_is_reproducible():
    a = FaultSchedule.seeded(42, n_requests=4)
    b = FaultSchedule.seeded(42, n_requests=4)
    assert a.events == b.events
    c = FaultSchedule.seeded(43, n_requests=4)
    assert a.events != c.events
    d = FaultSchedule.seeded(7, n_requests=3, paged=False)
    assert all(e.kind != "exhaust_pool" for e in d.events)


# -- front-end surfacing ---------------------------------------------------

def test_frontend_backpressure_carries_retry_hint():
    from repro.launch.serve import AsyncServingFrontend, Backpressure
    eng = _engine("bounded1", slots=1, max_len=32, max_queue=1,
                  megastep_k=4)

    async def drive():
        fe = AsyncServingFrontend(eng, max_pending=8,
                                  drain_hint_s=0.25)
        p = np.asarray([1, 2, 3], np.int32)
        tasks = [asyncio.ensure_future(
            fe.generate(p, max_new_tokens=4)) for _ in range(3)]
        out = await asyncio.gather(*tasks, return_exceptions=True)
        await fe.close()
        return out

    out = asyncio.run(drive())
    shed = [e for e in out if isinstance(e, Backpressure)]
    done = [t for t in out if isinstance(t, list)]
    assert shed and done            # some shed, some served
    assert all(e.retry_after_s is not None and e.retry_after_s > 0
               for e in shed)       # hint from drain_hint_s fallback
    assert all(len(t) == 4 for t in done)


def test_frontend_surfaces_poisoned_request_failure():
    from repro.launch.serve import AsyncServingFrontend, RequestFailed
    cfg, m, params = _model()
    eng = _engine("dense2k4", slots=2, max_len=64, megastep_k=4)

    async def drive():
        fe = AsyncServingFrontend(eng, max_pending=4)
        p = np.asarray([1, 2, 3, 4], np.int32)
        task = asyncio.ensure_future(
            fe.generate(p, max_new_tokens=6))
        while not fe._live:          # wait for admission
            await asyncio.sleep(0.001)
        eng.inject_logit_poison(fe._live[0].req)
        try:
            await task
            return None
        except RequestFailed as e:
            return e
        finally:
            await fe.close()

    err = asyncio.run(drive())
    assert err is not None
    assert err.reason == "nonfinite-logits"


def test_parser_overload_knobs():
    from repro.launch.serve import build_parser
    args = build_parser().parse_args([])
    assert args.max_queue == 0 and args.audit is False
    args = build_parser().parse_args(["--max-queue", "8", "--audit"])
    assert args.max_queue == 8 and args.audit is True

