"""Tiny CPU copies of the benchmark, for its own tests.

``tiny_root`` writes a checkout-like directory whose ``BENCHMARK.json``
(with the pending cells added), traffic mixes and metric readers are the
benchmark's own, and whose
configuration files keep every key but cut the network with the program's
``make_cnn(scale=...)`` and the input to ``hw`` pixels.  ``steer`` points
``bench.run`` at it and at the CPU.  Nothing here is a measurement: the peak
table it writes has a ``cpu`` entry only so that the arithmetic runs.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from repro.models.cnn import make_cnn

REPO = Path(__file__).resolve().parents[1]
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10, "flops_dtype": "float32"}
# cells not (yet) in the benchmark (PERF.md, Open questions), which the
# rehearsals still drive: YOLOv3, and the four-stage pipeline on four host
# devices
PENDING_CELLS = [
    {
        "name": "yolov3.serve.1chip",
        "config": "yolov3",
        "traffic": "closed_1stage_4micro",
        "chips": 1,
        "why": "closed loop, 4 microbatches of one 416x416 image per call, 1 stage",
    },
    {
        "name": "resnet50.pipe4.4chip",
        "config": "resnet50",
        "traffic": "closed_4stage_8micro",
        "chips": 4,
        "why": "the Alg. 1 seed cut on four chips: ppermute between stages and the final psum",
    },
]
PENDING_CONFIGS = [{"name": "yolov3", "file": "bench/configs/yolov3.json"}]


def tiny_config(config: dict, scale: float, hw: int) -> dict:
    specs = make_cnn(config["network"], scale=scale).specs
    return dict(
        config,
        scale=scale,
        input_hw=hw,
        layers=[[s.name, s.h_out, s.w_out, s.c_in, s.r, s.s, s.k, s.stride] for s in specs],
    )


def tiny_root(dst: Path, scale: float = 0.05, hw: int = 16) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for sub in ("traffic", "metrics"):
        shutil.copytree(REPO / "bench" / sub, dst / "bench" / sub, dirs_exist_ok=True)
    (dst / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    bench["configs"] += PENDING_CONFIGS
    for entry in bench["configs"]:
        config = json.loads((REPO / entry["file"]).read_text())
        (dst / entry["file"]).write_text(json.dumps(tiny_config(config, scale, hw)))
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    peaks["devices"] = {"cpu": CPU_PEAKS}
    (dst / "bench" / "peaks.json").write_text(json.dumps(peaks))
    # the pending cells all serve: they report what the serve cells report
    bench["workloads"] += PENDING_CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("images_per_s", "call_p90_ms") or m.get("moves") in ("setup_s", "images_per_s"):
            m["workloads"] += [c["name"] for c in PENDING_CELLS]
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dst


def steer(monkeypatch, root: Path) -> None:
    """Point ``bench.run`` at a tiny root and at the CPU, for one test."""
    from bench import run, work

    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "use_checkout_cache", lambda: "off in this rehearsal")
    monkeypatch.setattr(work, "PEAKS_FILE", root / "bench" / "peaks.json")
