"""The comparison that decides ``correct`` on the solo cell (one slot, a
q4_0 model over a q8_0 KV cache, greedy), driven through a whole run of
the harness at a size the CPU holds: the faults of ``test_check.py``
that exist at one slot, planted under the timed path, are not correct.

A step that returns its state unchanged writes no row of the quantized
cache (payloads or scales) in place, and a token altered where it is
produced. Half of the batch left out has no second half at one slot,
so it is not run here.

Also: the live cache positions the program counts in its
``engine.drain`` spans (``attended``) are those the harness's
``window.StepProbe`` reconstructs from outside the engine."""
import jax
import pytest

from perfbench import generator, run, system, weights, work
from perfbench.tests import tiny
from perfbench.tests.test_check import CELLS, _altered_token, _frozen_cache


@pytest.mark.parametrize("fault", [_frozen_cache, _altered_token],
                         ids=["state-unchanged", "token-altered"])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    jax.clear_caches()
    try:
        res = tiny.run(tiny.cell(*CELLS["solo"]))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > tiny.LIMIT


class _Span:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps each span's
    name and stats."""
    spans = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, dict(stats)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        _Span.spans.append(self)

    def set_metadata(self, **stats):
        self.stats.update(stats)


def test_drain_attended_matches_the_step_probe(monkeypatch):
    cell = tiny.cell(*CELLS["solo"])
    conf, mix = cell.config, cell.mix
    cfg = system.model_config(conf)
    engine = system.build_engine(cfg, lambda: weights.make(7, conf), conf,
                                 mix)
    specs = generator.schedule(mix, 7, 2.0, work.dims(conf).V)
    _Span.spans = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Span)
    recs, _, _, probe, _ = run.run_window(engine, mix, specs, 2.0, False,
                                          {"armed": False})
    drains = [s.stats["attended"] for s in _Span.spans
              if s.name == "engine.drain"]
    assert len(drains) == len(probe.steps) > 2
    assert sum(drains) == sum(s.attended for s in probe.steps) > 0
    # step by step too: one drain a step at pipeline depth 1
    assert drains == [s.attended for s in probe.steps]
    assert sum(len(r.tokens) for r in recs) > 0
