"""Run one cell of the benchmark once, on the chip, and print its result.

    python3 -m bench.run --workload resnet50.serve.1chip --seed 7 --seconds 30 --trace 0

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), the driver the mix names
(``bench/drivers/<driver>.py``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).  Adding a configuration, a mix or a metric
adds files and entries and edits none.

The run sets up (weights from ``--seed``, every program compiled and warmed),
measures for ``--seconds``, reads the peak memory, and then checks the
window's answers against ``bench/reference.py``.  With ``--trace 0`` the
metrics are the cell's end-to-end ones; with ``--trace 1`` its per-layer ones,
read from a profiler trace of the window's first seconds.  The last line of
standard output is one JSON object; the numbers compared, each with its
limit, are the last lines of standard error and the result's last key.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
PLATFORM = "tpu"
# JAX's persistent compilation cache, inside the checkout at a fixed path.
# Its size is capped as on the chip machines (192 MiB): a pipeline program
# with the weights as constants (~258 MB for ResNet-50) is then never
# written, where it would be a new entry for every seed.
CACHE_DIR = ROOT / ".jax_cache"
CACHE_MAX_BYTES = 192 * 2**20

sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.launch import compile_cache  # noqa: E402

from . import harness  # noqa: E402


def load_reader(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_spec(name: str, root: Path | None = None) -> dict:
    """The cell, its configuration and traffic, and its metrics, by name."""
    root = root or ROOT
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def in_cell(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": json.loads((root / config_entry["file"]).read_text()),
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def use_checkout_cache() -> str:
    """Keep compiled programs in the checkout's own cache, so that only a
    cell's first run there compiles them; the program's
    ``enable_compile_cache`` takes the directory given to it."""
    os.environ[compile_cache.ENV_VAR] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    return compile_cache.enable_compile_cache()


def require_devices(n_chips: int) -> list:
    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} count={len(devices)}", file=sys.stderr)
    if d.platform != PLATFORM:
        raise SystemExit(f"bench: needs a {PLATFORM} device, JAX found {d.platform}")
    if len(devices) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} chips, JAX found {len(devices)}")
    return devices


def per_layer_metrics(entries: list[dict], facts: dict) -> dict:
    metrics = {}
    for m in entries:
        value = load_reader(ROOT / "bench" / "metrics" / f"{m['name']}.py").read(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def result_line(spec: dict, run: harness.Run, outcome: harness.Outcome, all_devices: list) -> dict:
    checks = dict(outcome.checks)
    checks["window_compile_s"] = (run.facts["window_compile_s"], 0.0)
    correct = all(value <= limit for value, limit in checks.values()) and outcome.failed == 0
    d = all_devices[0]
    device = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(all_devices),
        "memory_peak_bytes": run.facts["memory_peak_bytes"],
    }
    if run.trace:
        reduction = run.facts.get("trace")
        metrics = per_layer_metrics(spec["per_layer"], run.facts)
        if reduction is not None:
            device["busy_s"] = reduction.busy_s
            device["window_s"] = reduction.window_s
    else:
        values = dict(outcome.end_to_end, setup_s=run.facts["setup_s"])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in spec["end_to_end"]}
    line = {
        "correct": bool(correct),
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace and run.facts.get("trace") is not None:
        line["breakdown"] = run.facts["trace"].breakdown()
    line["checks"] = {name: {"value": v, "limit": lim} for name, (v, lim) in checks.items()}
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = cell_spec(args.workload)
    all_devices = require_devices(spec["cell"]["chips"])
    backend_s = time.perf_counter() - T0
    print(f"[cache] {use_checkout_cache()}", file=sys.stderr)
    clock = harness.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        run = harness.Run(
            config=spec["config"],
            traffic=spec["traffic"],
            seed=args.seed,
            seconds=args.seconds,
            trace=bool(args.trace),
            devices=all_devices[: spec["cell"]["chips"]],
            clock=clock,
            t0=T0,
        )
        run.facts["setup_phases"] = {"backend": backend_s}
        driver = importlib.import_module(f".drivers.{spec['traffic']['driver']}", __package__)
        outcome = driver.drive(run)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)

    line = result_line(spec, run, outcome, all_devices)
    f = run.facts
    phases = ", ".join(f"{k} {v:.3f}" for k, v in f.get("setup_phases", {}).items())
    print(f"[setup] {phases}; compile {f['compile_s']:.3f} s; window {f['window_s']:.3f} s", file=sys.stderr)
    if f.get("trace") is not None:
        print(f"[trace] {f.get('traced_s', 0.0):.3f} s profiled, busy {f['trace'].busy_s:.6f} s of {f['trace'].window_s:.6f} s", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
