"""The plain reference, against JAX's own ops and against the program's
pipeline at a tiny size on CPU host devices."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("src, dst", [((7, 7, 5), (1, 1, 5)), ((112, 112, 8), (56, 56, 8)), ((9, 6, 4), (4, 10, 7))])
def test_nearest_is_half_pixel_nearest(src, dst):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, *src))
    want = jax.image.resize(x, (2, *dst), "nearest")
    np.testing.assert_array_equal(reference.nearest(x, *dst), want)


@pytest.mark.parametrize("hw, r, stride", [(16, 7, 2), (15, 3, 1), (16, 3, 2), (9, 1, 1), (13, 3, 2)])
def test_conv_same_is_a_same_convolution(hw, r, stride):
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (2, hw, hw, 5))
    w = jax.random.normal(kw, (r, r, 5, 6))
    want = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest"
    )
    got = reference.conv_same(x, w, stride)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_high_is_three_bf16_passes():
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (1, 8, 8, 256))
    w = jax.random.normal(kw, (1, 1, 256, 64))
    exact = np.einsum("nhwc,ck->nhwk", np.asarray(x, np.float64), np.asarray(w[0, 0], np.float64))
    one_pass = np.einsum(
        "nhwc,ck->nhwk",
        np.asarray(x.astype(jnp.bfloat16), np.float64),
        np.asarray(w[0, 0].astype(jnp.bfloat16), np.float64),
    )
    errs = {p: reference.rel_err(reference.conv_same(x, w, 1, p), exact) for p in reference.PRECISIONS}
    assert errs["highest"] < 1e-6 < errs["high"] < reference.rel_err(one_pass, exact) / 10
    with pytest.raises(ValueError, match="unknown precision"):
        reference.conv_same(x, w, 1, "default")


def test_rel_err_refuses_wrong_shapes_and_non_finite():
    a = np.ones((2, 3))
    assert reference.rel_err(a, a) == 0.0
    assert reference.rel_err(a[:1], a) == float("inf")
    assert reference.rel_err(np.full((2, 3), np.nan), a) == float("inf")


AGREE = """
import json, sys
import numpy as np
import jax
from bench import harness, reference
from repro.core import PipelineConfig
from repro.launch.mesh import make_stage_mesh
from repro.models.cnn import canonical_pipeline_apply
from repro.pipeline import PipelineRunner

config, stages = json.loads(sys.argv[1]), json.loads(sys.argv[2])
rows = reference.layer_rows(config)
model = harness.program_model(config)
params = harness.he_weights(rows, 5)
in_shape = (config["input_hw"], config["input_hw"], 3)
apply_fn, to_canon, crop_out, _ = canonical_pipeline_apply(model, params, in_shape)
conf = PipelineConfig(stages=tuple(stages), eps=tuple(range(len(stages))))
runner = PipelineRunner(mesh=make_stage_mesh(conf.depth), conf=conf, apply_layer=apply_fn, n_micro=3)
x = np.random.default_rng(0).standard_normal((3, 2, *in_shape), dtype=np.float32)
with jax.default_matmul_precision("highest"):
    got = np.asarray(crop_out(runner.run(jax.vmap(to_canon)(x))))
want = np.asarray(reference.make_forward(rows)(params, x.reshape(6, *in_shape))).reshape(got.shape)
print(json.dumps({"rel_err": reference.rel_err(got, want), "scale": float(np.abs(want).max())}))
"""


@pytest.mark.parametrize(
    "name, scale, stages",
    [("resnet50", 0.05, [50]), ("resnet50", 0.05, [17, 12, 12, 9]), ("yolov3", 0.04, [13, 13, 13, 13])],
)
def test_reference_agrees_with_the_pipeline(name, scale, stages):
    from bench import rehearsal

    config = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    config = rehearsal.tiny_config(config, scale, hw=16)
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.pathsep.join([str(REPO / "src"), str(REPO)]),
    }
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(AGREE), json.dumps(config), json.dumps(stages)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # He-scaled weights keep the output on the scale of the input
    assert out["scale"] > 0.1
    assert out["rel_err"] < config["limits"]["serve_rel_err"]
