"""``bench.run``: its refusals, its result line, and ``BENCHMARK.json``.

The benchmark refuses to run without a TPU.  The rehearsals steer it from
here (``bench.rehearsal``): CPU, tiny configurations, a short window.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from bench import rehearsal, run

REPO = Path(__file__).resolve().parents[1]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _env(**extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **extra}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_refuses_without_tpu():
    r = subprocess.run(
        [*BENCH["command"], "--workload", "resnet50.serve.1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=_env(), cwd=REPO,
    )
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "needs a tpu device" in r.stderr


def test_refuses_with_only_its_own_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [*BENCH["command"], "--workload", "resnet50.serve.1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=_env(PYTHONPATH=""), cwd=tmp_path,
    )
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def _reader(name: str):
    spec = importlib.util.spec_from_file_location("reader", REPO / "bench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_benchmark_json_is_whole():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"]
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        config = json.loads((REPO / c["file"]).read_text())
        assert NAME.match(c["name"]) and config["name"] == c["name"]
        assert c["reduced"] == config["reduced"] == sorted(config["departures"])
        assert config["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        traffic = json.loads((REPO / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (REPO / "bench" / "drivers" / f"{traffic['driver']}.py").exists()
        assert len(w["why"]) <= 200
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    reported = {}
    for m in BENCH["end_to_end"]:
        assert m["source"] in {"host_clock", "device_trace"} and 0.01 <= m["bound"] <= 0.25
        for w in m.get("workloads", cells):
            reported.setdefault(w, set()).add(m["name"])
    assert all("setup_s" in names and len(names) >= 2 for names in reported.values())
    for m in BENCH["per_layer"]:
        reader = _reader(m["name"])
        assert (m["unit"], m["layer"], m["source"], m["moves"]) == (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES)
        assert m["source"] in SOURCES and NAME.match(m["name"])
        for w in m["workloads"]:
            assert m["moves"] in reported[w], (m["name"], w)
    for w in cells:
        assert any(w in m["workloads"] for m in BENCH["per_layer"])


def _rehearse(monkeypatch, capsys, tmp_path, workload, trace, seed=2**33 + 5):
    rehearsal.steer(monkeypatch, rehearsal.tiny_root(tmp_path))
    assert run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.5", "--trace", str(trace)]) == 0
    captured = capsys.readouterr()
    return _last_json(captured.out), captured.err


@pytest.mark.parametrize("workload", ["resnet50.serve.1chip", "yolov3.serve.1chip", "resnet50.tune.1chip"])
@pytest.mark.parametrize("trace", [0, 1])
def test_one_chip_rehearsal(monkeypatch, capsys, tmp_path, workload, trace):
    line, err = _rehearse(monkeypatch, capsys, tmp_path, workload, trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == len(jax.devices())
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("[check] window_compile_s 0.0 limit 0.0")
    spec = run.cell_spec(workload)
    if trace:
        # no TPU plane in a CPU trace: the device readers find nothing and say nothing
        assert "busy_s" not in line["device"]
        assert set(line["metrics"]) <= {m["name"] for m in spec["per_layer"]}
        assert "compile_s" in line["metrics"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in spec["end_to_end"]}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_four_chip_rehearsal(tmp_path):
    code = textwrap.dedent(
        f"""
        import sys, pytest
        from pathlib import Path
        from bench import rehearsal, run
        mp = pytest.MonkeyPatch()
        rehearsal.steer(mp, rehearsal.tiny_root(Path({str(tmp_path)!r})))
        sys.exit(run.main(["--workload", "resnet50.pipe4.4chip", "--seed", "3", "--seconds", "0.5"]))
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                 PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)])),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = _last_json(r.stdout)
    assert line["correct"] is True and line["device"]["count"] == 4


NEW_READER = '''"""Calls per traced second."""
UNIT = "calls/s"
LAYER = "whole call"
SOURCE = "host_clock"
MOVES = "images_per_s"


def read(facts):
    return facts["calls"] / facts["window_s"]
'''


def test_adding_a_cell_adds_files_only(monkeypatch, capsys, tmp_path):
    """A new configuration, traffic mix and per-layer metric: new files and
    new entries in BENCHMARK.json, no file of the harness edited."""
    root = rehearsal.tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((REPO / "bench" / "configs" / "resnet50.json").read_text())
    config = rehearsal.tiny_config(dict(config, name="synthnet", network="synthnet"), scale=0.1, hw=24)
    (root / "bench" / "configs" / "synthnet.json").write_text(json.dumps(config))
    mix = {"driver": "serve_closed", "stages": 1, "n_micro": 3, "batch": 2, "pool": 2, "sample": 2}
    (root / "bench" / "traffic" / "closed_1stage_3micro.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "calls_per_s.py").write_text(NEW_READER)
    bench["configs"].append({"name": "synthnet", "source": "s", "file": "bench/configs/synthnet.json", "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "synthnet.serve", "config": "synthnet", "traffic": "closed_1stage_3micro", "chips": 1, "why": "w"})
    for m in bench["end_to_end"]:
        m.get("workloads", []).append("synthnet.serve")
    bench["per_layer"].append({"name": "calls_per_s", "unit": "calls/s", "better": "higher", "source": "host_clock",
                               "layer": "whole call", "moves": "images_per_s", "workloads": ["synthnet.serve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rehearsal.steer(monkeypatch, root)
    assert run.main(["--workload", "synthnet.serve", "--seed", "9", "--seconds", "0.5", "--trace", "1"]) == 0
    line = _last_json(capsys.readouterr().out)
    assert line["correct"] is True and line["metrics"]["calls_per_s"]["value"] > 0
