"""The program's spans in a trace, and the readers and checks built on
them, on a small trace written out by hand: one chip, a window of
10 us, two megasteps, host spans with numeric stats and device ops at
known places (times in ns)."""
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import devtrace, spancheck, spans, spec, window
from perfbench.generator import RequestSpec

# (name, start, end, {stat: value}) on the host's python thread
HOST = [
    ("bench.window", 1000, 11000, {}),
    ("engine.stage", 900, 1000, {"admitted": 1}),      # before the window
    ("engine.admit", 950, 960, {"uid": 1, "queued_us": 100.0}),
    ("engine.dispatch", 1000, 1200, {"megastep": 1}),
    ("engine.drain_wait", 1200, 5600, {"megastep": 1}),
    ("engine.drain", 5600, 5700, {"emitted": 8, "fed": 3}),
    ("frontend.publish", 5750, 5800, {"n": 8}),
    ("frontend.admit", 5820, 5880, {}),
    ("engine.submit", 5010, 5020, {"uid": 2}),
    ("engine.submit", 5030, 5040, {"uid": 3}),
    ("engine.stage", 5900, 6000, {"admitted": 2}),
    ("engine.admit", 5950, 5955, {"uid": 2, "queued_us": 300.0}),
    ("engine.admit", 5960, 5965, {"uid": 3, "queued_us": 500.0}),
    ("engine.dispatch", 6000, 6100, {"megastep": 2}),
    ("engine.drain_wait", 6100, 10600, {"megastep": 2}),
    ("engine.drain", 10600, 10700, {"emitted": 6, "fed": 0}),
    ("engine.first_token", 10650, 10655,
     {"uid": 2, "prefill_us": 2000.0, "ttft_us": 5.64}),
    ("engine.first_token", 10660, 10665,
     {"uid": 3, "prefill_us": 4000.0, "ttft_us": 5.63}),
]
MEGASTEPS = [(1300, 5500), (6200, 10500)]
# (HLO text, start, end) on the op line
OPS = [
    ("%fusion.8 = bf16[64,256]{1,0} fusion(s8[64,256]{1,0} %p), "
     "kind=kLoop", 2000, 2400),
    ("%fusion.8 = bf16[64,256]{1,0} fusion(s8[64,256]{1,0} %p), "
     "kind=kLoop", 7000, 7400),
    ("%sort.3 = f32[8,256]{1,0} sort(f32[8,256]{1,0} %l), dimensions={1}",
     3000, 3100),
    ("%sort.3 = f32[8,256]{1,0} sort(f32[8,256]{1,0} %l), dimensions={1}",
     8000, 8100),
    ("%while.9 = (s32[]) while((s32[]) %t), body=%b", 2900, 3200),
    ("%add.1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)", 3300, 3400),
]
STAT_IDS = {"admitted": 101, "uid": 102,
            "queued_us": 104, "megastep": 105, "emitted": 106, "fed": 107,
            "n": 108, "prefill_us": 109, "ttft_us": 110}


def _stat(key, value):
    kind = ("str_value" if isinstance(value, str) else
            "double_value" if isinstance(value, float) else "int64_value")
    value = f'"{value}"' if isinstance(value, str) else value
    return f"stats {{ metadata_id: {STAT_IDS[key]} {kind}: {value} }} "


def _event(meta, start, end, stats):
    return (f"events {{ metadata_id: {meta} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} "
            + "".join(_stat(k, v) for k, v in stats.items()) + "}\n")


def _metadata(names):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}\n' for n, i in names.items())


def _stat_metadata():
    return "".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{k}" }} }}\n' for k, i in STAT_IDS.items())


def _proto(host=HOST) -> str:
    ops = {}
    for text, _, _ in OPS:
        ops.setdefault(text, len(ops) + 1)
    op_events = "".join(_event(ops[t], s, e, {}) for t, s, e in OPS)
    mods = "".join(_event(50, s, e, {}) for s, e in MEGASTEPS)
    names = {}
    for n, _, _, _ in host:
        names.setdefault(n, len(names) + 1)
    host_events = "".join(_event(names[n], s, e, st)
                          for n, s, e, st in host)
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{op_events} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{mods} }}
{_metadata(ops)}
  event_metadata {{ key: 50 value {{ id: 50 name: "jit__megastep_impl(1)" }} }}
{_stat_metadata()}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
{host_events} }}
{_metadata(names)}
{_stat_metadata()}
}}
'''


def _write(tmp_path, proto):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(proto))
    return str(path)


@pytest.fixture
def obs(tmp_path):
    path = _write(tmp_path, _proto())
    return SimpleNamespace(trace=devtrace.reduce(path), trace_path=path,
                           records=[], t0=0.0, t1=1.0)


def read(name, o):
    return spec.reader(name).read(o)


def test_spans_in_the_window_with_their_stats(obs):
    found = spans.of(obs)
    # the stage and admit that started before the window are left out
    assert [s.stats for s in found["engine.stage"]] == [{"admitted": 2}]
    assert spans.stat(obs, "engine.admit", "uid") == [2, 3]
    assert spans.stat(obs, "engine.dispatch", "megastep") == [1, 2]
    assert [(s.start, s.end) for s in found["engine.drain"]] == \
        [(5600, 5700), (10600, 10700)]
    assert spans.of(obs) is found          # read once per run


def test_readers_of_program_spans(obs):
    # end of drain_wait(1) at 5600 -> end of dispatch(2) at 6100
    assert read("host_turnaround_ms", obs) == pytest.approx(500e-6)
    assert read("queue_wait_p90_ms", obs) == pytest.approx(
        1e-3 * np.percentile([300.0, 500.0], 90))
    assert read("prefill_p90_ms", obs) == pytest.approx(
        1e-3 * np.percentile([2000.0, 4000.0], 90))


def test_nothing_to_read_reads_as_none(tmp_path):
    untraced = SimpleNamespace(trace=None, trace_path=None)
    program_less = [h for h in HOST if not h[0].startswith(
        ("engine.", "frontend."))]
    path = _write(tmp_path, _proto(program_less))
    parent = SimpleNamespace(trace=devtrace.reduce(path), trace_path=path)
    for name in ("host_turnaround_ms", "queue_wait_p90_ms",
                 "prefill_p90_ms"):
        assert read(name, untraced) is None
        assert read(name, parent) is None


def test_idle_gaps_named_by_program_spans(obs):
    gaps = spancheck.idle_gaps(obs.trace, spans.of(obs))
    # the longest, [3400, 7000) between the op line's ops, has its
    # midpoint in megastep 1's drain wait; every gap has a program span
    assert gaps[0] == ["engine.drain_wait", pytest.approx(3600e-9)]
    assert all(name.startswith("engine.") for name, _ in gaps)
    # the same gaps as the harness's breakdown, which names them only
    # by its own spans
    outside = devtrace.breakdown(obs.trace)["idle_gaps"]
    assert [t for _, t in gaps] == [t for _, t in outside]
    assert {name for name, _ in outside} == {"outside the harness spans"}


def test_trace_found_where_the_traced_run_writes_it(tmp_path, monkeypatch):
    from perfbench import run
    path = _write(tmp_path, _proto())
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    o = SimpleNamespace(trace=devtrace.reduce(path), records=[],
                        t0=0.0, t1=1.0)
    assert spans.trace_file(o) == path
    assert read("host_turnaround_ms", o) == pytest.approx(500e-6)
    assert spans.trace_file(SimpleNamespace(trace=None)) is None


def test_clock_and_gap_decomposition(obs):
    found = spans.of(obs)
    execs = obs.trace.matching(devtrace.load_rules()["kernels"]["megastep"])
    c = spancheck.clock(found, execs)
    assert c["share"] == 100.0
    assert c["dispatch_start_to_device_start"]["median"] == \
        pytest.approx(250e-6)
    assert c["device_end_to_drain_wait_end"]["max"] == pytest.approx(100e-6)
    g = spancheck.gaps(found, execs)
    want = {"drain_wait_tail": 100, "drain": 100, "front_end": 200,
            "frontend_spans": 110, "stage_admitting": 100, "dispatch": 100,
            "dispatch_end_to_device_start": 100, "device_idle": 700}
    assert g.pop("stage_idle") is None          # the one stage admitted
    assert g.pop("overlapped") == 0
    # one publish of 50 ns streamed 8 tokens
    assert g.pop("publish_us_per_token") == pytest.approx(50e-3 / 8)
    assert {k: v["median"] for k, v in g.items()} == pytest.approx(
        {k: v * 1e-6 for k, v in want.items()})


def test_pipelined_megasteps_have_no_turnaround(tmp_path):
    # at pipeline depth 2 megastep 2 is dispatched (at 5000) before
    # megastep 1's drain wait ends (at 5600): no serial turnaround
    host = [h if h[0] != "engine.dispatch" or h[3]["megastep"] != 2
            else ("engine.dispatch", 5000, 5100, {"megastep": 2})
            for h in HOST]
    path = _write(tmp_path, _proto(host))
    o = SimpleNamespace(trace=devtrace.reduce(path), trace_path=path)
    assert read("host_turnaround_ms", o) is None
    execs = o.trace.matching(devtrace.load_rules()["kernels"]["megastep"])
    g = spancheck.gaps(spans.of(o), execs)
    assert g["overlapped"] == 1 and g["device_idle"] is None


def test_clock_check_sees_a_device_op_outside_its_spans(tmp_path):
    host = [h if h[0] != "engine.drain_wait" or h[3]["megastep"] != 2
            else ("engine.drain_wait", 6100, 10400, {"megastep": 2})
            for h in HOST]
    path = _write(tmp_path, _proto(host))
    o = SimpleNamespace(trace=devtrace.reduce(path), trace_path=path)
    execs = o.trace.matching(devtrace.load_rules()["kernels"]["megastep"])
    c = spancheck.clock(spans.of(o), execs)
    assert c["share"] == 50.0
    assert c["device_end_to_drain_wait_end"]["min"] == pytest.approx(-100e-6)


def _rec(due, sent, first):
    s = RequestSpec(index=0, prompt=np.ones(4, np.int32), max_new=8,
                    temperature=0.0, top_k=0, due_s=due, in_window=True)
    return window.Record(spec=s, due=due, sent=sent, times=[first],
                         tokens=[1], done=True)


def test_reconcile_client_and_program(obs):
    # uid 1 was sent before the window and has no first-token span;
    # uid 2: client 5.8 us (due -> first token), program 5.6 us
    obs.t0, obs.t1 = 1.0, 1.0 + 10e-6
    obs.records = [_rec(0.5, 0.5, 0.9),
                   _rec(1.0 + 3.9e-6, 1.0 + 4.0e-6, 1.0 + 9.7e-6),
                   _rec(1.0 + 4.0e-6, 1.0 + 4.03e-6, 1.0 + 9.6e-6)]
    r = spancheck.reconcile(spans.of(obs), obs.trace.window[0], obs)
    assert r["client_minus_program"]["n"] == 2
    assert r["client_minus_program"]["min"] == pytest.approx(-30e-6,
                                                             abs=1e-9)
    assert r["client_minus_program"]["max"] == pytest.approx(160e-6,
                                                             abs=1e-9)
    # due 3900 and 4000 ns into the window; submitted 4010 and 4030
    assert r["due_to_submit"]["min"] == pytest.approx(30e-6, abs=1e-9)
    assert r["due_to_submit"]["max"] == pytest.approx(110e-6, abs=1e-9)
    # first tokens streamed 9700 and 9600 ns into the window; the
    # program stamped them 9650 and 9660 ns into it
    assert r["first_token_offset"]["min"] == pytest.approx(-60e-6,
                                                           abs=1e-9)
    assert r["first_token_offset"]["max"] == pytest.approx(50e-6,
                                                           abs=1e-9)
    # the two parts add up to the whole, request by request
    assert r["due_to_submit"]["max"] + r["first_token_offset"]["max"] == \
        pytest.approx(r["client_minus_program"]["max"], abs=1e-9)


def test_slot_shares_counted_inside(obs):
    # two engine steps of 8 substeps over 2 slots: 32 slot-substeps;
    # the drains fed 3 prompt tokens and emitted 8 + 6
    obs.t0, obs.t1, obs.slots = 0.0, 1.0, 2
    obs.steps = [window.Step(0.1 * i, 0.1 * i + 0.05, 8, 0, 0, 0.0, True)
                 for i in range(2)]
    r = spancheck.inside(spans.of(obs), obs)
    assert r["prefill_slot_share"] == pytest.approx(100 * 3 / 32)
    assert r["slot_busy_share"] == pytest.approx(100 * 17 / 32)
