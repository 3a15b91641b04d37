"""The reduction from a profiler trace to busy time, idle share and
kernel time, on a small trace written out by hand: one chip, a window
of 10 us, and ops at known places."""
from types import SimpleNamespace

import pytest

from perfbench import devtrace, spec

QMM = ('%closed_call.3 = bf16[8,16]{1,0} custom-call(bf16[8,64]{1,0} %a, '
       's8[32,16]{1,0} %b, bf16[2,16]{1,0} %c), '
       'custom_call_target=\\"tpu_custom_call\\"')
ATT = ('%closed_call.4 = bf16[8,4,1,16]{3,2,1,0} custom-call(s32[8]{0} %l, '
       'bf16[8,4,1,16]{3,2,1,0} %q, bf16[8,2,64,16]{3,2,1,0} %k, '
       'bf16[8,2,64,16]{3,2,1,0} %v), custom_call_target=\\"tpu_custom_call\\"')
FUSION = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
LOOP = "%while.2 = (s32[]) while((s32[]) %t), body=%b"

# (metadata id, start ns, duration ns) on the op line; the window is
# [1000, 11000) ns. Busy: [1000,3000) + [2500,4000) + [6000,7000) +
# [9000,11000) (clipped from 12000) = 3000 + 1000 + 2000 = 6000 ns.
OPS = [(1, 500, 2500), (2, 2500, 1500), (3, 6000, 1000), (1, 9000, 3000),
       (4, 1000, 9000)]


def _proto(loop: bool = True) -> str:
    evs = "".join(
        f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} }}\n"
        for m, s, d in OPS if loop or m != 4)
    names = [FUSION, QMM, ATT, LOOP]
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}\n' for i, n in enumerate(names, 1))
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{evs} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 9 offset_ps: 1000000 duration_ps: 4000000 }}
    events {{ metadata_id: 9 offset_ps: 6000000 duration_ps: 5000000 }} }}
{meta}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit__megastep_impl(1)" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }}
    events {{ metadata_id: 2 offset_ps: 4500000 duration_ps: 1000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "engine.step" }} }}
}}
'''


@pytest.fixture
def reduction(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_proto()))
    return devtrace.reduce(str(path))


def test_busy_union_and_window(reduction):
    assert reduction.window == (1000.0, 11000.0)
    # the loop op spans the whole window, so busy is all of it
    assert reduction.busy_ns == 10000.0


def test_union_and_gaps():
    iv = [(1000, 3000), (2500, 4000), (6000, 7000), (9000, 11000)]
    assert devtrace.union_ns(iv) == 6000
    assert devtrace.gaps(iv, 1000, 11000) == [(4000, 6000), (7000, 9000)]
    assert devtrace.clip([(500, 3000), (9000, 12000)], 1000, 11000) == \
        [(1000, 3000), (9000, 11000)]


def test_kernel_time_by_rule(reduction):
    rules = devtrace.load_rules()["kernels"]
    assert reduction.seconds(rules["quant_matmul"]) == \
        (pytest.approx(1500e-9), 1)
    assert reduction.seconds(rules["decode_attention"]) == \
        (pytest.approx(1000e-9), 1)
    assert reduction.seconds(rules["megastep"]) == (pytest.approx(9000e-9), 2)


def test_idle_share_and_breakdown(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _proto(loop=False)))
    red = devtrace.reduce(str(path))
    assert red.busy_ns == 6000.0
    obs = SimpleNamespace(trace=red)
    idle = spec.reader("device_idle_share").read(obs)
    assert idle == pytest.approx(40.0)
    b = devtrace.breakdown(red)
    names = [n for n, _ in b["device_ops"]]
    assert names[0].startswith("%fusion.1")          # 1500 + 2000 ns
    assert any(n.startswith("quant_matmul") for n in names)
    # the longest gap [4000, 6000) holds the host's engine.step span
    assert b["idle_gaps"][0] == ["engine.step", pytest.approx(2e-6)]
    assert b["idle_gaps"][1][0] == "outside the harness spans"


def test_loop_ops_left_out_of_top_ops(reduction):
    names = [n for n, _ in devtrace.breakdown(reduction)["device_ops"]]
    assert not any("while" in n for n in names)
