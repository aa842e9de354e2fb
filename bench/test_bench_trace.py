"""The reduction from a profiler trace to device time.

A hand-built trace checks each rule against numbers worked out by hand; a
small trace recorded on a TPU v5e (``testdata/``, three calls of a two-layer
program) checks that the reading finds the device's operations, their layer
scopes and the host's spans where the chip's profiler puts them.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace
from bench.trace import Op

RECORDED = Path(__file__).resolve().parent / "testdata" / "v5e_two_layers.xplane.pb"
RECORDED_HLO = RECORDED.with_name("v5e_two_layers.hlo.txt")


def hand_trace():
    # two devices, window 0..10 s from the host spans
    spans = [(0.0, 4.0, "call"), (4.0, 6.0, "crop_d2h"), (6.0, 10.0, "call")]
    ops = [
        Op(0, "convolution.1", "jit(f)/shard_map/L1/conv_general_dilated", 1.0, 3.0),
        Op(0, "fusion.7", "jit(f)/shard_map/L1/add", 2.0, 4.0),  # overlaps the conv by 1 s
        Op(0, "while.3", "jit(f)/shard_map/while", 0.5, 4.5),  # holds the two above: busy time only
        Op(0, "collective-permute-start.2", "jit(f)/shard_map/ppermute", 7.0, 8.0),
        Op(1, "convolution.3", "jit(f)/shard_map/L2/conv_general_dilated", 5.0, 9.0),
        Op(1, "all-reduce.4", "jit(f)/shard_map/psum", 9.0, 11.0),  # 1 s outside the window
        Op(2, "convolution.9", "jit(f)/L2/x", 0.0, 10.0),  # a device the cell does not use
    ]
    return ops, spans


def test_hand_built_trace():
    ops, spans = hand_trace()
    r = trace.reduce(ops, spans, ["L1", "L2"], n_devices=2)
    assert r.window_s == 10.0
    # device 0 busy 0.5..4.5 and 7..8 = 5 s; device 1 busy 5..10 = 5 s
    assert r.busy_s == pytest.approx(5.0)
    assert r.idle_share == pytest.approx(0.5)
    assert r.scope_s == pytest.approx({"L1": 4.0, "L2": 4.0})
    assert r.collective_s == pytest.approx(2.0)
    assert r.op_s == pytest.approx(
        {"L1/convolution": 2.0, "L1/fusion": 2.0, "L2/convolution": 4.0, "ppermute/collective-permute-start": 1.0, "psum/all-reduce": 1.0}
    )
    # device 0 idle 0..0.5 (call), 4.5..7 (middle in crop_d2h), 8..10 (call);
    # device 1 idle 0..5 (middle 2.5 in call): halves, as a mean over two devices
    assert r.idle_by_span == pytest.approx({"call": (0.5 + 2 + 5) / 2, "crop_d2h": 2.5 / 2})
    b = r.breakdown()
    assert b["device_ops"][0] == ["L2/convolution", 4.0]
    assert b["idle_gaps"][0] == ["call", 3.75]


def test_nothing_to_read_gives_none():
    assert trace.reduce([], [(0.0, 1.0, "call")], ["L1"], n_devices=1) is None
    ops, spans = hand_trace()
    assert trace.reduce(ops, spans, ["L1"], n_devices=2).scope_s == pytest.approx({"L1": 4.0})


@pytest.mark.parametrize("name, kind, collective", [
    ("fusion.12", "fusion", False),
    ("collective-permute-done.3", "collective-permute-done", True),
    ("all-reduce", "all-reduce", True),
    ("convolution.4.1", "convolution", False),
])
def test_op_kinds(name, kind, collective):
    assert trace.op_kind(name) == kind
    assert trace.is_collective(name) is collective


HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.0 = f32[8]{0} tanh(f32[8]{0} %p), metadata={op_name="jit(f)/layerA/tanh" stack_frame_id=3}
}

ENTRY %main.5 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  ROOT %fusion.2 = f32[8]{0} fusion(f32[8]{0} %x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/layerB/sin"}
}
"""


def test_op_names_from_hlo_text():
    assert trace.instruction("%fusion.13 = f32[112,8,15,64]{3,1,2,0} fusion(f32[7] %copy-done), kind=kOutput") == "fusion.13"
    assert trace.instruction("  ROOT %tanh.0 = f32[8]{0} tanh(f32[8]{0} %p)") == "tanh.0"
    assert trace.module_key("jit_f(16222868935708491181)") == trace.module_key("HloModule jit_f, entry") == "jit_f"
    assert trace.op_names([HLO]) == {"jit_f": {"tanh.0": "jit(f)/layerA/tanh", "x.1": "x", "fusion.2": "jit(f)/layerB/sin"}}


def test_recorded_tpu_trace():
    ops, spans = trace.read_profile(RECORDED, trace.op_names([RECORDED_HLO.read_text()]))
    assert [s[2] for s in spans] == ["call"] * 3
    assert ops and {op.device for op in ops} == {0}
    assert all(op.path for op in ops if op.name.startswith("fusion"))
    r = trace.reduce(ops, spans, ["layerA", "layerB"], n_devices=1)
    assert set(r.scope_s) == {"layerA", "layerB"}
    assert 0 < r.busy_s < r.window_s
    assert r.window_s == pytest.approx(max(b for _, b, _ in spans) - min(a for a, _, _ in spans))
    assert r.collective_s == 0.0
