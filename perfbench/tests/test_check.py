"""The comparison that decides ``correct``, driven through a whole run
of the harness at a size the CPU holds (the chip check skipped): sound
runs pass, the control in the program's place does not, and so does
every fault a serving cell can have, planted under the timed path, on
greedy rows and on sampled rows alike.

Faults: a step that returns its state unchanged (the KV cache is never
written); half of the batch left out (the upper half of the slots gets
the lower half's logits); a token altered where it is produced. The
exchange between chips does not exist on one chip."""
import jax
import jax.numpy as jnp
import pytest

from perfbench.tests import tiny

CELLS = {"chat": ("deepseek-7b.q8_0", "chat"),
         "solo": ("mistral-nemo-12b.q4_0", "solo")}


@pytest.mark.parametrize("which", sorted(CELLS))
def test_sound_run_is_correct(which):
    res = tiny.run(tiny.cell(*CELLS[which]))
    assert res["correct"], res["checks"]
    assert res["checks"]["checked_tokens"]["value"] > 0
    assert res["checks"]["logit_gap"]["value"] <= tiny.LIMIT
    assert list(res)[-1] == "checks"
    assert {"tokens_per_s", "itl_p95_ms", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("which", sorted(CELLS))
def test_control_in_the_programs_place_is_not_correct(which):
    res = tiny.run(tiny.cell(*CELLS[which]), control=True)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > tiny.LIMIT


def test_sampled_rows_are_checked_within_their_top_k():
    res = tiny.run(tiny.cell(*CELLS["chat"], greedy_share=0.0))
    assert res["correct"], res["checks"]
    assert res["checks"]["checked_tokens"]["value"] > 0


def _frozen_cache(monkeypatch):
    from repro.models import attention
    def write(cache, k, v, slot, **kw):
        return {n: cache[n] for n in ("k", "v", "k_scale", "v_scale")
                if n in cache}
    monkeypatch.setattr(attention, "kv_cache_write", write)


def _wrap_logits(monkeypatch, edit):
    from repro.models.model import Model
    orig = Model.decode_step

    def decode_step(self, params, tokens, cache, advance_mask=None):
        logits, cache = orig(self, params, tokens, cache, advance_mask)
        return edit(logits), cache
    monkeypatch.setattr(Model, "decode_step", decode_step)


def _half_batch(monkeypatch):
    def edit(logits):
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]) if h else logits
    _wrap_logits(monkeypatch, edit)


def _altered_token(monkeypatch):
    _wrap_logits(monkeypatch, lambda logits: logits.at[:, 5].add(100.0))


@pytest.mark.parametrize("greedy_share", [0.5, 0.0],
                         ids=["greedy-and-sampled", "sampled-only"])
@pytest.mark.parametrize("fault", [_frozen_cache, _half_batch,
                                   _altered_token],
                         ids=["state-unchanged", "half-batch",
                              "token-altered"])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, fault,
                                                   greedy_share):
    fault(monkeypatch)
    jax.clear_caches()
    try:
        # arrivals fast enough to keep every slot busy
        res = tiny.run(tiny.cell(*CELLS["chat"], rate_per_s=40.0,
                                 greedy_share=greedy_share))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > tiny.LIMIT
