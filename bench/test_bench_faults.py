"""The check fails a broken timed path.

Each test drives a whole run at a tiny size on the CPU, with one fault
planted in the program underneath, and sees ``correct`` come out false:
an answer altered where it is produced, half the batch left out, the
pipeline handing back its input unchanged, the exchange between chips left
out, and, in the tuning cell, an altered layer program and a tuned cut that
loses a layer.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import pytest

from bench import rehearsal, run
from repro.core import PipelineConfig
from repro.models.cnn import CNNModel
from repro.pipeline import PipelineRunner

REPO = Path(__file__).resolve().parents[1]


def _altered(out):
    return out.at[(0,) * out.ndim].add(1.0)


def _half_batch(out):
    return out.at[out.shape[0] // 2 :].set(0.0)


SERVE_FAULTS = {
    "answer_altered": lambda run_, micro: _altered(run_(micro)),
    "half_batch": lambda run_, micro: _half_batch(run_(micro)),
    "input_unchanged": lambda run_, micro: micro,
}


def _drive(monkeypatch, capsys, tmp_path, workload):
    rehearsal.steer(monkeypatch, rehearsal.tiny_root(tmp_path))
    assert run.main(["--workload", workload, "--seed", "21", "--seconds", "0.3"]) == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serve_fault_is_not_correct(monkeypatch, capsys, tmp_path, fault):
    original = PipelineRunner.run
    monkeypatch.setattr(PipelineRunner, "run", lambda self, micro: SERVE_FAULTS[fault](lambda m: original(self, m), micro))
    line = _drive(monkeypatch, capsys, tmp_path, "resnet50.serve.1chip")
    assert line["correct"] is False
    assert line["checks"]["serve_rel_err"]["value"] > line["checks"]["serve_rel_err"]["limit"]


def test_four_chip_exchange_left_out(tmp_path):
    code = textwrap.dedent(
        f"""
        import json, sys, jax, pytest
        from pathlib import Path
        from bench import rehearsal, run
        mp = pytest.MonkeyPatch()
        mp.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)
        rehearsal.steer(mp, rehearsal.tiny_root(Path({str(tmp_path)!r})))
        sys.exit(run.main(["--workload", "resnet50.pipe4.4chip", "--seed", "4", "--seconds", "0.3"]))
        """
    )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.pathsep.join([str(REPO / "src"), str(REPO)]),
    }
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["device"]["count"] == 4


def test_tune_altered_layer_is_not_correct(monkeypatch, capsys, tmp_path):
    original = CNNModel.apply_layer

    def altered(self, i, p, x, **kw):
        y = original(self, i, p, x, **kw)
        return _altered(y) if i == 10 else y

    monkeypatch.setattr(CNNModel, "apply_layer", altered)
    line = _drive(monkeypatch, capsys, tmp_path, "resnet50.tune.1chip")
    assert line["correct"] is False
    assert line["checks"]["layer_rel_err"]["value"] > line["checks"]["layer_rel_err"]["limit"]


def test_tune_cut_losing_a_layer_is_not_correct(monkeypatch, capsys, tmp_path):
    from bench.drivers import tune_repeat

    original = tune_repeat.run_shisha

    def lossy(*args, **kw):
        conf = original(*args, **kw).result.best_conf
        first = max(range(conf.depth), key=lambda s: conf.stages[s])
        stages = tuple(n - (s == first) for s, n in enumerate(conf.stages))
        return types.SimpleNamespace(result=types.SimpleNamespace(best_conf=PipelineConfig(stages, conf.eps)))

    monkeypatch.setattr(tune_repeat, "run_shisha", lossy)
    line = _drive(monkeypatch, capsys, tmp_path, "resnet50.tune.1chip")
    assert line["correct"] is False
    assert line["checks"]["bad_cuts"]["value"] > 0
