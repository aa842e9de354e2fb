"""The control of the check, at a size a test run can hold: the reference in
three bfloat16 passes, put in the program's place, fails each cell's limit on
three seeds.  (The program passes it at this size: ``test_bench_run.py``.)"""

from __future__ import annotations

import json

import pytest

from bench import control, rehearsal


@pytest.mark.parametrize("workload", ["resnet50.serve.1chip", "yolov3.serve.1chip", "resnet50.tune.1chip"])
def test_control_fails_the_limit(monkeypatch, capsys, tmp_path, workload):
    rehearsal.steer(monkeypatch, rehearsal.tiny_root(tmp_path))
    assert control.main(["--workload", workload, "--seeds", "1", str(2**31 + 11), "77"]) == 0
    readings = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert len(readings) == 3
    assert all(r["fails"] and r["value"] > r["limit"] for r in readings), readings
