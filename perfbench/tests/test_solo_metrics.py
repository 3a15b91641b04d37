"""The solo cell's per-layer readers on a small trace written out by hand:
one chip, a window of 20 us, two megasteps of 8 substeps at one slot, a
q4_0 and a q8_0 ``quant_matmul`` call, attention over a q8_0 (``s8``)
and a bf16 cache, and ``engine.drain`` spans that carry ``attended``
(times in ns). Expected values are worked out by hand below from the
tiny NeMo configuration: 2 layers, hidden 64, ffn 128, 4 query heads
and 2 KV heads of 16, vocabulary 512, q4_0 weights and a q8_0 cache."""
from types import SimpleNamespace

import pytest

from perfbench import devtrace, spec, window
from perfbench.tests import tiny

PEAK_FLOPS, HBM = 197e12, 819e9          # TPU v5 lite, peaks.json

QMM = ('%{name} = bf16[1,128]{{1,0}} custom-call(bf16[1,64]{{1,0}} %a, '
       's8[{rows},128]{{1,0}} %b, bf16[2,128]{{1,0}} %c), '
       'custom_call_target=\\"tpu_custom_call\\"')
Q4_0 = QMM.format(name="closed_call.1", rows=32)     # K/2 rows: q4_0
Q8_0 = QMM.format(name="closed_call.2", rows=64)     # K rows: q8_0
ATTN = ('%{name} = bf16[1,2,2,16]{{3,2,1,0}} custom-call(s32[2]{{0}} %l, '
        'bf16[1,2,2,16]{{3,2,1,0}} %q, {cache}[2,1,2,64,16]{{4,3,2,1,0}} '
        '%k), custom_call_target=\\"tpu_custom_call\\"')
ATTN_S8 = ATTN.format(name="closed_call.3", cache="s8")
ATTN_BF16 = ATTN.format(name="closed_call.4", cache="bf16")

OPS = [(Q4_0, 2000, 3000), (Q4_0, 12000, 13000),     # 2000 ns of q4_0
       (Q8_0, 4000, 9000),
       (ATTN_S8, 5000, 7000), (ATTN_S8, 14000, 16000),   # 4000 ns of s8
       (ATTN_BF16, 17000, 19000),
       (Q4_0, 500, 900)]                               # before the window
MEGASTEPS = [(1500, 9500), (11500, 19500)]             # 8000 ns each
HOST = [("bench.window", 1000, 21000, {}),
        ("engine.drain", 600, 700, {"attended": 50}),  # before the window
        ("engine.drain", 9600, 9700, {"attended": 100}),
        ("engine.drain", 19600, 19700, {"attended": 200})]
STEP = window.Step
# (start s, end s, substeps, fed, advanced, attended, pending); the
# window is [0, 10) s on the host clock
STEPS = [STEP(-1.0, -0.5, 8, 0, 8, 400.0, True),       # before the window
         STEP(0.0, 1.0, 8, 0, 8, 100.0, True),
         STEP(1.0, 2.0, 8, 0, 5, 200.0, False)]        # done after 5


def _event(meta, start, end, stats):
    st = "".join(f"stats {{ metadata_id: 101 int64_value: {v} }} "
                 for v in stats.values())
    return (f"events {{ metadata_id: {meta} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} {st}}}\n")


def _metadata(names):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}\n' for n, i in names.items())


def _proto(ops, host) -> str:
    op_ids, host_ids = {}, {}
    for text, _, _ in ops:
        op_ids.setdefault(text, len(op_ids) + 1)
    for name, _, _, _ in host:
        host_ids.setdefault(name, len(host_ids) + 1)
    stat = 'stat_metadata { key: 101 value { id: 101 name: "attended" } }'
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{"".join(_event(op_ids[t], s, e, {}) for t, s, e in ops)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{"".join(_event(50, s, e, {}) for s, e in MEGASTEPS)} }}
{_metadata(op_ids)}
  event_metadata {{ key: 50 value {{ id: 50 name: "jit__megastep_impl(1)" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
{"".join(_event(host_ids[n], s, e, st) for n, s, e, st in host)} }}
{_metadata(host_ids)}
{stat}
}}
'''


def _obs(tmp_path, ops=OPS, host=HOST, steps=STEPS):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _proto(ops, host)))
    return SimpleNamespace(
        trace=devtrace.reduce(str(path)), trace_path=str(path),
        conf=tiny.config("mistral-nemo-12b.q4_0"), steps=list(steps),
        t0=0.0, t1=10.0, slots=1, device_kind="TPU v5 lite")


def read(name, obs):
    return spec.reader(name).read(obs)


def test_token_device_ms(tmp_path):
    # 16000 ns over 2 megasteps, 8 substeps each
    assert read("token_device_ms", _obs(tmp_path)) == \
        pytest.approx(8000e-9 * 1e3 / 8)


def test_megastep_roofline_mfu(tmp_path):
    # weights a layer: qkv 64x128 + o 64x64 + gate|up 64x256 + down
    # 128x64 = 36864; 2 layers and the 64x512 head = 106496 at 0.5625
    # bytes (q4_0): 59904 bytes a substep that runs a token, 8 + 5 of
    # them. K and V of a q8_0 position in a layer: 2 x 2 heads x (16 +
    # 1 bf16 scale of 2 bytes) = 72 bytes, over 2 layers and the 300
    # positions attended in the window.
    nbytes = 59904 * 13 + 2 * 300 * 72
    flops = 2 * 106496 * 13 + 2 * 4 * 4 * 16 * 300
    assert nbytes / HBM > flops / PEAK_FLOPS         # bound by memory
    assert read("megastep_roofline_mfu", _obs(tmp_path)) == \
        pytest.approx(100 * (nbytes / HBM) / 16000e-9)


def test_q4_0_matmul_roofline_ignores_q8_0_calls(tmp_path):
    obs = _obs(tmp_path)
    # x (1, 64) @ w (64, 128) in q4_0: 64 x 128 x 0.5625 bytes of
    # weight, 64 x 2 in and 128 x 2 out; two calls of 1000 ns
    least = 2 * (64 * 128 * 0.5625 + 64 * 2 + 128 * 2) / HBM
    assert read("q4_0_matmul_roofline", obs) == \
        pytest.approx(100 * least / 2000e-9)
    # the same calls with the q8_0 one read differently
    assert read("quant_matmul_roofline", obs) != \
        pytest.approx(read("q4_0_matmul_roofline", obs))


def test_quant_kv_attention_roofline(tmp_path):
    # the program's 300 positions attended in the window, 72 bytes each
    # in each of 2 layers, over the s8-cache calls' 4000 ns; the bf16
    # cache's call is not counted
    least = 2 * 300 * 72 / HBM
    assert read("quant_kv_attention_roofline", _obs(tmp_path)) == \
        pytest.approx(100 * least / 4000e-9)


@pytest.mark.parametrize("name,ops,host", [
    ("token_device_ms", OPS, HOST),
    ("megastep_roofline_mfu", OPS, HOST),
    ("q4_0_matmul_roofline", [o for o in OPS if o[0] != Q4_0], HOST),
    ("quant_kv_attention_roofline", [o for o in OPS if o[0] != ATTN_S8],
     HOST),
    # a program whose drain spans carry no ``attended``
    ("quant_kv_attention_roofline", OPS,
     [(n, s, e, {}) for n, s, e, _ in HOST]),
], ids=["token-no-steps", "mfu-no-steps", "no-q4_0-call",
        "no-s8-cache-call", "no-attended-stat"])
def test_nothing_to_read_reads_as_none(tmp_path, name, ops, host):
    obs = _obs(tmp_path, ops, host,
               steps=[] if name in ("token_device_ms",
                                    "megastep_roofline_mfu") else STEPS)
    assert read(name, obs) is None
    obs.trace = None
    assert read(name, obs) is None
