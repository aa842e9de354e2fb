"""The flat canonical activation of the CNN stage pipeline.

Every image on a stage boundary is held as one zero-padded (R, 128) block,
whose R * 128 elements are the largest boundary activation rounded up to 128.
These tests pin that size at full width from abstract params, the exact
round trip through the canonical, the pipeline's bit-identity with the
sequential model on 1 and 4 stages, and how small the compiled 1-stage
ResNet-50 runner program is.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PipelineConfig
from repro.launch.mesh import make_stage_mesh
from repro.models.cnn import CNNModel, canonical_pipeline_apply, make_cnn
from repro.pipeline import PipelineRunner

from test_program_spans import on_host_devices

RESNET50_IN = (224, 224, 3)


def abstract_params(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def boundary_shapes(model, in_shape):
    return [in_shape] + [(sp.h_out, sp.w_out, sp.k) for sp in model.specs]


@pytest.mark.parametrize(
    "net,in_shape,largest",
    [
        ("resnet50", RESNET50_IN, 802_816),  # 112x112x64 = 56x56x256
        ("yolov3", (416, 416, 3), 5_537_792),  # 208x208x128
    ],
)
def test_canon_holds_the_largest_activation(net, in_shape, largest):
    model = make_cnn(net)
    *_, canon = canonical_pipeline_apply(model, abstract_params(model), in_shape)
    assert max(np.prod(s) for s in boundary_shapes(model, in_shape)) == largest
    assert canon == (-(-largest // 128), 128)


@pytest.mark.parametrize("net,in_shape", [("resnet50", RESNET50_IN), ("yolov3", (416, 416, 3))])
def test_crop_inverts_to_canon_at_every_boundary(net, in_shape):
    """A chain cut after layer i crops to layer i's output (i = 0: the input)."""
    model = make_cnn(net)
    full_canon = canonical_pipeline_apply(model, abstract_params(model), in_shape)[3]
    rng = np.random.default_rng(0)
    for i, shape in enumerate(boundary_shapes(model, in_shape)):
        head = CNNModel(specs=model.specs[:i])
        _, to_canon, crop_out, canon = canonical_pipeline_apply(head, abstract_params(head), in_shape)
        x = jnp.asarray(rng.standard_normal((2, *shape), dtype=np.float32))
        xc = to_canon(x)
        assert xc.shape == (2, *canon) and canon[0] <= full_canon[0]
        assert np.array_equal(np.asarray(crop_out(xc)), np.asarray(x)), (i, shape)
        assert not np.asarray(xc).reshape(2, -1)[:, x[0].size :].any(), "canonical padding is not zero"


@pytest.mark.parametrize("stages", [(50,), (17, 12, 12, 9)])
def test_resnet50_pipeline_bit_identical_to_sequential(stages):
    code = f"""
        import jax, numpy as np
        from repro.core import PipelineConfig
        from repro.launch.mesh import make_stage_mesh
        from repro.models.cnn import canonical_pipeline_apply, make_cnn
        from repro.pipeline import PipelineRunner

        model = make_cnn("resnet50", scale=0.05)
        params = model.init(jax.random.PRNGKey(0))
        in_shape = (10, 10, 3)
        stages = {stages!r}
        conf = PipelineConfig(stages=stages, eps=tuple(range(len(stages))))
        apply_fn, to_canon, crop_out, _ = canonical_pipeline_apply(model, params, in_shape)
        runner = PipelineRunner(mesh=make_stage_mesh(len(stages)), conf=conf, apply_layer=apply_fn, n_micro=4)
        raw = jax.random.normal(jax.random.PRNGKey(1), (4, 2) + in_shape)
        out = np.asarray(crop_out(runner.run(jax.vmap(to_canon)(raw))))
        ref = np.stack([np.asarray(model(params, raw[i])) for i in range(4)])
        print(out.shape == ref.shape and np.array_equal(out, ref))
        """
    assert on_host_devices(len(stages), code) == "True"


def test_resnet50_runner_program_is_small():
    """Full width, 1 stage, 8 microbatches of 1 image, compiled for the host.

    A box of each dimension's maximum (224x224x2048 an image) would make it
    about 10.8 GB.  The pad of each layer's output meets the next layer's
    slice inside a stage, so the compiler folds the pair away.
    """
    model = make_cnn("resnet50")
    params = abstract_params(model)
    conf = PipelineConfig(stages=(len(model.specs),), eps=(0,))
    mesh = make_stage_mesh(1)
    canon = canonical_pipeline_apply(model, params, RESNET50_IN)[3]

    def program(params, micro):
        apply_fn, *_ = canonical_pipeline_apply(model, params, RESNET50_IN)
        return PipelineRunner(mesh=mesh, conf=conf, apply_layer=apply_fn, n_micro=8).run(micro)

    micro = jax.ShapeDtypeStruct((8, 1, *canon), jnp.float32)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(program).lower(params, micro).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < 0.5e9, total
    assert len(re.findall(r"= \S+ pad\(", compiled.as_text())) <= 4
