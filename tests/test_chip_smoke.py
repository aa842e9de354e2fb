"""Rehearsals of ``chip_smoke.py`` on the CPU, and its compile-cache helper.

The script refuses to run without a TPU.  These tests steer it from here:
they point its device check at the CPU and cut ResNet-50 to a tiny scale,
then run every phase, on one host device and on four.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"

# what the tests change in chip_smoke before calling its main()
TINY = """
chip_smoke.PLATFORM = "cpu"
chip_smoke.SCALE = 0.05
chip_smoke.IN_HW = 16
chip_smoke.enable_compile_cache = lambda: "off in this rehearsal"
"""


def _run(args, cwd, env_extra=None, timeout=300):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(args, capture_output=True, text=True, timeout=timeout, env=env, cwd=cwd)


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_refuses_without_tpu():
    r = _run([sys.executable, str(SCRIPT)], cwd=REPO)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a tpu device" in r.stderr


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env_extra={"PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_one_chip_rehearsal(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    exec(TINY, {"chip_smoke": chip_smoke})
    chip_smoke.main([])
    out = capsys.readouterr().out
    assert "[tune] schedule" in out and "trials" in out
    assert out.count("max rel err") == chip_smoke.N_CALLS
    # count is whatever this process has: another test module may have forced host devices
    count = len(jax.devices())
    assert _last_json(out) == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": count}}


def test_four_chip_rehearsal():
    code = textwrap.dedent(
        f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
        chip_smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(chip_smoke)
        """
    ) + TINY + 'chip_smoke.main(["--chips", "4"])\n'
    r = _run([sys.executable, "-c", code], cwd=REPO, env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "[tune]" not in r.stdout  # --chips 4 runs the pipeline path only
    for s in range(4):
        assert f"stage {s}: layers" in r.stdout and f"device id={s}" in r.stdout
    assert r.stdout.count("max rel err") == 3
    assert _last_json(r.stdout)["device"]["count"] == 4


@pytest.fixture
def restore_cache_config():
    saved = {
        name: getattr(jax.config, name)
        for name in ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    }
    yield
    for name, value in saved.items():
        jax.config.update(name, value)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
