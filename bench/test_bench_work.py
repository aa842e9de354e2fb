"""The benchmark's operation and byte counts, against hand values."""

from __future__ import annotations

import json

import pytest

from bench import harness, reference, work

CONFIGS = work.PEAKS_FILE.parent / "configs"


def rows_of(name: str) -> list[dict]:
    return reference.layer_rows(json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize(
    "name, n_layers, gflop, params_m",
    [("resnet50", 50, 6.99662336, 22.757736), ("yolov3", 52, 49.031610368, 40.567072)],
)
def test_totals_match_hand_values(name, n_layers, gflop, params_m):
    rows = rows_of(name)
    assert len(rows) == n_layers
    assert work.total_flops(rows) == pytest.approx(gflop * 1e9, rel=1e-12)
    assert work.total_flops(rows, batch=8) == 8 * work.total_flops(rows)
    params = sum(r["r"] * r["s"] * r["c_in"] * r["k"] + r["k"] for r in rows)
    assert params == pytest.approx(params_m * 1e6, rel=1e-12)


def test_stem_bytes_and_bound_by_hand():
    stem = rows_of("resnet50")[0]
    # 224x224x3 in, 112x112x64 out, 7x7x3x64 weights + 64 biases, 4 bytes each
    assert work.bytes_moved(stem) == 4 * (224 * 224 * 3 + 112 * 112 * 64 + 7 * 7 * 3 * 64 + 64)
    assert work.flops(stem) == 2 * 112 * 112 * 3 * 7 * 7 * 64
    peak = work.peaks("TPU v5 lite")
    # 236 MFLOP over 3.85 MB: 61 operations a byte, under the v5e's 197e12 / 819e9 = 240,
    # so bandwidth bounds it
    assert work.least_seconds(stem, peak) == pytest.approx(work.bytes_moved(stem) / 819e9)
    wide = dict(stem, h_out=8, w_out=8, c_in=4096, r=1, s=1, k=4096, stride=1)
    assert work.least_seconds(wide, peak, batch=4096) == pytest.approx(work.flops(wide, 4096) / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


@pytest.mark.parametrize("name", ["resnet50", "yolov3"])
def test_layer_table_is_the_programs(name):
    config = json.loads((CONFIGS / f"{name}.json").read_text())
    assert len(harness.program_model(config).specs) == len(config["layers"])
    broken = dict(config, layers=[list(r) for r in config["layers"]])
    broken["layers"][3][6] += 1
    with pytest.raises(ValueError, match="differ"):
        harness.program_model(broken)
