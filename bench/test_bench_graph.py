"""The layer-graph cell (``yolov3_det.serve.1chip``), rehearsed on the CPU.

The tiny configuration is built here at an input the graph's strides,
upsamples and concats line up on (64x64, grids 64 ... 2).  ``bench.run``
rehearses the cell with and without a trace, and the program agrees with
``reference_graph``; each fault planted in the program underneath (its
layer graph as the configuration states it) makes ``correct`` false; the
reference in three bfloat16 passes fails the limit.

The runs of this file share their seeded weights and their compiled
references (``shared_compiles``): the faults are in the program, so every
run of one seed is checked against the same reference answers.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from bench import control_graph, harness, reference_graph, rehearsal, run
from repro.models import cnn
from repro.models.cnn import CNNModel

CELL = "yolov3_det.serve.1chip"
SCALE, HW = 0.05, 64


@pytest.fixture(scope="module", autouse=True)
def shared_compiles():
    """Weights by seed and shapes, and jitted references by graph and
    precision, made once for the whole file."""
    weights, forwards = {}, {}
    he_weights, make_forward = harness.he_weights, reference_graph.make_forward

    def cached_weights(rows, seed, device=None):
        key = (seed, tuple((r["r"], r["s"], r["c_in"], r["k"]) for r in rows))
        if key not in weights:
            weights[key] = he_weights(rows, seed, device)
        return weights[key]

    def cached_forward(rows, precision="highest"):
        key = (json.dumps(rows, sort_keys=True), precision)
        if key not in forwards:
            forwards[key] = make_forward(rows, precision)
        return forwards[key]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "he_weights", cached_weights)
        mp.setattr(reference_graph, "make_forward", cached_forward)
        yield


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("graph"))
    config = json.loads((rehearsal.REPO / "bench" / "configs" / "yolov3_det.json").read_text())
    (root / "bench" / "configs" / "yolov3_det.json").write_text(json.dumps(rehearsal.tiny_config(config, SCALE, HW)))
    return root


def _run(monkeypatch, capsys, root, trace=0, seed=2**33 + 7) -> dict:
    """One run of the cell, on the seed every run of this file shares."""
    rehearsal.steer(monkeypatch, root)
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2", "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_graph_cell_rehearsal(monkeypatch, capsys, root, trace):
    line = _run(monkeypatch, capsys, root, trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["serve_rel_err"]["value"] <= line["checks"]["serve_rel_err"]["limit"]
    spec = run.cell_spec(CELL)
    if trace:
        # no TPU plane in a CPU trace: the device readers, skip_share among them, say nothing
        assert set(line["metrics"]) == {"compile_s"}
    else:
        assert set(line["metrics"]) == {m["name"] for m in spec["end_to_end"]} == {"images_per_s", "call_p90_ms", "setup_s"}


def _index(name):
    return next(i for i, sp in enumerate(cnn.yolov3_det_specs()) if sp.name == name)


def _patch_layer(monkeypatch, change):
    """Layer ``i``'s output as ``change(original, self, i, p, *xs)`` makes it,
    where ``change`` returns None to leave a layer as it is."""
    original = CNNModel.apply_layer

    def apply_layer(self, i, p, *xs, **kw):
        y = change(original, self, i, p, *xs, **kw)
        return original(self, i, p, *xs, **kw) if y is None else y

    monkeypatch.setattr(CNNModel, "apply_layer", apply_layer)


def _no_shortcut(original, self, i, p, *xs, **kw):
    if i == _index("res256_3_3x3"):
        return original(self, i, p, *xs[:-1], jnp.zeros_like(xs[-1]), **kw)


def _bilinear(original, self, i, p, *xs, **kw):
    if self.specs[i].up > 1:
        y = original(self, i, p, *xs, **kw)[:, ::2, ::2, :]
        return jax.image.resize(y, (y.shape[0], 2 * y.shape[1], 2 * y.shape[2], y.shape[3]), "bilinear")


def _same_pad(original, self, i, p, *xs, **kw):
    if i == _index("down128"):
        specs = list(self.specs)
        specs[i] = dataclasses.replace(specs[i], pad="same")
        return original(CNNModel(specs=tuple(specs)), i, p, *xs, **kw)


def _stale_route(monkeypatch):
    """The 52-scale route reads res256_6_3x3 where the graph says res256_7_3x3."""
    original = cnn.sources
    late, early = _index("res256_7_3x3"), _index("res256_6_3x3")
    monkeypatch.setattr(
        cnn, "sources", lambda specs, i: tuple(early if t == late else t for t in original(specs, i))
    )


FAULTS = {
    "dropped_shortcut_add": lambda mp: _patch_layer(mp, _no_shortcut),
    "route_reads_res256_6": _stale_route,
    "bilinear_upsample": lambda mp: _patch_layer(mp, _bilinear),
    "leaky_slope_0.2": lambda mp: mp.setattr(cnn, "LEAKY_SLOPE", 0.2),
    "same_pad_on_stride_2": lambda mp: _patch_layer(mp, _same_pad),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_graph_fault_is_not_correct(monkeypatch, capsys, root, fault):
    FAULTS[fault](monkeypatch)
    line = _run(monkeypatch, capsys, root)
    assert line["correct"] is False
    assert line["checks"]["serve_rel_err"]["value"] > line["checks"]["serve_rel_err"]["limit"]


def test_graph_control_fails_the_limit(monkeypatch, capsys, root):
    rehearsal.steer(monkeypatch, root)
    assert control_graph.main(["--workload", CELL, "--seeds", str(2**33 + 7), str(2**31 + 11), "77"]) == 0
    readings = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert len(readings) == 3
    assert all(r["fails"] and r["value"] > r["limit"] for r in readings), readings
