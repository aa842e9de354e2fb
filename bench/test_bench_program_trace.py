"""The program's own spans and scopes, read from a profiler trace.

A hand-built trace checks each rule of ``bench/program_trace.py`` against
numbers worked out by hand; the recorded TPU v5e traces (``testdata/``)
check that the reading finds the program's spans and scopes where the
chip's profiler puts them, and that it leaves ``bench/trace.py``'s
numbers as they are.
"""

from __future__ import annotations

import json
import os
import random
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import program_run, program_trace, rehearsal, trace
from bench.program_trace import Device, Span
from bench.trace import Op

REPO = Path(__file__).resolve().parents[1]
TESTDATA = REPO / "bench" / "testdata"
OLD = TESTDATA / "v5e_two_layers.xplane.pb"
OLD_HLO = TESTDATA / "v5e_two_layers.hlo.txt"
RECORDED = TESTDATA / "v5e_program.xplane.pb"
RECORDED_HLO = TESTDATA / "v5e_program.hlo.txt"


def hand_trace():
    # two devices, window 0..10 s from the drivers' spans
    host = [(0.0, 4.0, "call"), (4.0, 6.0, "crop_d2h"), (6.0, 10.0, "call")]
    program = [
        Span("shisha.run", 0.0, 0.5, {}),
        Span("shisha.measure", 6.0, 10.0, {}),
        Span("shisha.measure.warm", 6.0, 7.0, {"layer": 0}),
        Span("shisha.measure.timed", 7.0, 9.5, {"layer": 0, "calls": 4}),
    ]
    ops = [
        Op(0, "while.1", "jit(f)/while", 0.9, 3.6),  # holds the next four: busy time only
        Op(0, "fusion.1", "jit(f)/while/body/shisha.inject/dynamic_slice", 1.0, 2.0),
        Op(0, "fusion.2", "jit(f)/while/body/L1/shisha.canon/pad", 1.5, 2.5),  # overlaps the inject by 0.5 s
        Op(0, "convolution.1", "jit(f)/while/body/L1/conv_general_dilated", 2.5, 3.0),
        Op(0, "collective-permute-start.1", "jit(f)/while/body/shisha.ship/ppermute", 3.0, 3.5),
        Op(0, "fusion.3", "jit(g)/L2/add", 7.5, 8.0),
        Op(1, "all-reduce.1", "jit(f)/shisha.gather/psum", 5.0, 5.5),
        Op(2, "fusion.9", "jit(f)/shisha.emit/x", 0.0, 10.0),  # a device the cell does not use
    ]
    devices = {
        0: Device(modules=[(0.8, 3.8, "jit_f"), (7.0, 8.5, "jit_g")], lines={"DMA": [(3.65, 3.9)]}),
        1: Device(modules=[(4.8, 6.0, "jit_f")], lines={}),
    }
    return ops, host, program, devices


def test_hand_built_trace():
    ops, host, program, devices = hand_trace()
    r = program_trace.reduce(ops, host, program, devices, ["L1", "L2"], n_devices=2)
    old = trace.reduce(ops, host, ["L1", "L2"], n_devices=2)
    assert (r.window_s, r.busy_s) == (old.window_s, old.busy_s) == (10.0, pytest.approx(1.85))
    # device 0 idle inside jit_f 0.8..0.9 and 3.6..3.8, inside jit_g 7..7.5 and
    # 8..8.5 (1.3 s); device 1 inside jit_f 4.8..5 and 5.5..6 (0.7 s)
    assert r.in_program_idle_s == pytest.approx(1.0)
    assert r.idle_in_program == pytest.approx({"jit_f": 0.5, "jit_g": 0.5})
    # device 0: inject and canon overlap, 1..2.5, and ship 3..3.5; device 1: gather 0.5 s
    assert r.machinery_s == pytest.approx(1.25)
    # each gap's middle to the innermost span: 0.45 in shisha.run, not call
    assert r.idle_by_span == pytest.approx(
        {"shisha.run": 0.45, "crop_d2h": 1.95, "shisha.measure.timed": 3.25, "call": 2.5}
    )
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s)
    assert r.idle_between_programs == pytest.approx(
        {"shisha.run": 0.4, "crop_d2h": 1.6, "shisha.measure.timed": 2.75, "call": 2.4}
    )
    assert sum(r.idle_between_programs.values()) == pytest.approx(r.window_s - r.busy_s - r.in_program_idle_s)
    assert r.line_s_in_program_idle == pytest.approx({"DMA": 0.075})
    # by layer, else by the innermost shisha scope, else by the path's last step
    assert r.op_s == pytest.approx({
        "shisha.inject/fusion": 1.0, "L1/fusion": 1.0, "L1/convolution": 0.5,
        "shisha.ship/collective-permute-start": 0.5, "L2/fusion": 0.5, "shisha.gather/all-reduce": 0.5,
    })
    assert r.busy_in(7.0, 9.5) == pytest.approx(0.25)
    assert r.busy_in(0.0, 10.0) == pytest.approx(r.busy_s)
    b = r.breakdown()
    assert b["idle_gaps"][0] == ["shisha.measure.timed", pytest.approx(3.25)]
    assert {k for k, _ in b["idle_in_program"]} == {"jit_f", "jit_g"}


@pytest.mark.parametrize("name, want", [
    ("runner_machinery_share", 100 * 1.25 / 1.85),
    ("device_idle_in_program.serve", 100 * 1.0 / 10.0),
    ("measure_warm_share", 100 * 1.0 / (1.0 + 2.5)),
    ("sync_us_per_layer_call", 1e6 * (2.5 - 0.25) / 4),
])
def test_metrics_by_hand(name, want):
    ops, host, program, devices = hand_trace()
    r = program_trace.reduce(ops, host, program, devices, ["L1", "L2"], n_devices=2)
    _, _, read = program_trace.METRICS[name]
    assert read(r) == pytest.approx(want)


@pytest.mark.parametrize("name", list(program_trace.METRICS))
def test_metrics_silent_without_the_program(name):
    # the parent's program: no shisha scopes, no shisha spans, no modules
    ops, host, _, _ = hand_trace()
    ops = [Op(op.device, op.name, re.sub(r"shisha\.\w+/", "", op.path), op.start, op.end) for op in ops]
    r = program_trace.reduce(ops, host, [], {}, ["L1", "L2"], n_devices=2)
    _, _, read = program_trace.METRICS[name]
    assert read(r) is None
    assert program_trace.reduce([], host, [], {}, ["L1"], n_devices=1) is None


def test_span_index_is_span_at():
    rng = random.Random(3)
    spans = []
    for _ in range(300):
        a = rng.uniform(0.0, 1.0)
        spans.append((a, a + rng.choice([1e-4, 3e-3, 0.05, 0.4]), f"s{len(spans)}"))
    index = program_trace.SpanIndex(spans)
    for _ in range(2000):
        t = rng.uniform(-0.1, 1.5)
        assert index.at(t) == trace.span_at(spans, t)


def test_old_trace_reads_as_before():
    """A trace of a program without spans or scopes: the same window and busy
    time as bench/trace.py's, and nothing of the program's to read."""
    r = program_trace.reduce_file(OLD, ["layerA", "layerB"], 1, [OLD_HLO.read_text()])
    old = trace.reduce_file(OLD, ["layerA", "layerB"], 1, [OLD_HLO.read_text()])
    assert (r.window_s, r.busy_s) == (old.window_s, old.busy_s)
    assert r.program_spans == [] and r.machinery_s == 0.0
    assert program_trace.runner_machinery_share(r) is None and program_trace.measure_warm_share(r) is None
    assert 0.0 <= r.in_program_idle_s <= r.window_s - r.busy_s


def recorded():
    texts = re.split(r"(?m)^(?=HloModule )", RECORDED_HLO.read_text())
    return [t for t in texts if t.strip()]


def test_recorded_program_trace():
    """Three runner calls and one measured build on the chip
    (``testdata/record_v5e_program.py``)."""
    texts = recorded()
    r = program_trace.reduce_file(RECORDED, ["layerA", "layerB"], 1, texts)
    old = trace.reduce_file(RECORDED, ["layerA", "layerB"], 1, texts)
    assert (r.window_s, r.busy_s) == (old.window_s, old.busy_s)
    assert set(old.scope_s) == {"layerA", "layerB"}
    runs = r.spans("shisha.run")
    assert len(runs) == 3
    measure = r.spans("shisha.measure")
    assert len(measure) == 1
    for name in (program_trace.WARM, program_trace.TIMED):
        spans = r.spans(name)
        assert [s.meta["layer"] for s in spans] == [0, 1]
        assert all(measure[0].start <= s.start <= s.end <= measure[0].end for s in spans)
    assert [s.meta["calls"] for s in r.spans(program_trace.TIMED)] == [3, 3]
    # every program span lies inside a driver span of the same trace
    _, host = trace.read_profile(RECORDED)
    assert all(any(a <= s.start and s.end <= b for a, b, _ in host) for s in r.program_spans)
    # the runner's scopes name device operations, the layers keep theirs
    assert {k.split("/")[0] for k in r.op_s} >= {"layerA", "layerB", "shisha.inject", "shisha.emit", "shisha.init"}
    assert 0 < r.machinery_s <= r.busy_s
    assert 0 <= r.in_program_idle_s <= r.window_s - r.busy_s
    assert {k for k, _ in r.breakdown()["idle_gaps"]} & {program_trace.WARM, program_trace.TIMED, "shisha.measure"}
    for name, (_, _, read) in program_trace.METRICS.items():
        value = read(r)
        assert value is not None and value >= 0, name
        if name != "sync_us_per_layer_call":
            assert value <= 100, name


@pytest.mark.parametrize("workload", ["resnet50.serve.1chip", "resnet50.tune.1chip"])
def test_program_run_rehearsal(monkeypatch, capsys, tmp_path, workload):
    """On the CPU the trace has no TPU plane: the cell runs, the program's
    reading finds no device and says nothing, and the profiler's cost is
    still reported."""
    rehearsal.steer(monkeypatch, rehearsal.tiny_root(tmp_path))
    assert program_run.main(["--workload", workload, "--seed", str(2**33 + 9), "--seconds", "0.5"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result, extra = json.loads(lines[-2]), json.loads(lines[-1])
    assert result["correct"] is True
    assert extra["program_metrics"] == {}
    assert extra["tracing"]["window_per_s"] > 0 and extra["tracing"]["traced_per_s"] > 0


def test_program_run_refuses_without_tpu():
    """Run as the chip runs it, from the checkout with no path set: it finds
    the program by itself, and without a TPU it refuses."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-m", "bench.program_run", "--workload", "resnet50.tune.1chip", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert r.returncode != 0
    assert "needs a tpu device" in r.stderr
    assert '"correct"' not in r.stdout
