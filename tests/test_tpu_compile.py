"""Compile rehearsals for a TPU v5e, with no chip attached.

The TPU compiler is installed alongside JAX, and it compiles for a chip that
is described rather than attached.  These tests compile the conv kernel at
ResNet-50's widths and the full-width ResNet-50 pipeline program, so that a
kernel Mosaic refuses, or a program that outgrows one chip's 16 GB, fails
here and not on the chip.  Nothing runs, so nothing here is a measurement.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture, never while a module is imported.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import generate_seed, weights
from repro.kernels import ops
from repro.models.cnn import canonical_pipeline_apply, make_cnn, network_layers
from repro.pipeline import PipelineRunner
from repro.pipeline.hetero import tpu_platform_from_mesh

RUNNER_BYTES = 0.5e9  # of one v5e chip's 16 GB: weights and flat canonical buffers


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            jax.config.update("jax_enable_compilation_cache", was_enabled)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


# (H=W, C, R=S, K): ResNet-50's stride-1 conv widths at 224x224 input
@pytest.mark.parametrize("hw,c,rs,k", [(56, 64, 3, 64), (56, 64, 1, 256), (7, 512, 3, 512)])
def test_conv_kernel_compiles_at_resnet50_widths(one_chip, hw, c, rs, k):
    x = _sds((1, hw, hw, c), one_chip)
    w = _sds((rs, rs, c, k), one_chip)
    compiled = jax.jit(ops.conv2d_im2col).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = jax.eval_shape(ops.conv2d_im2col, x, w)
    assert out.shape == (1, hw, hw, k)


def test_conv_kernel_refuses_stride_two_stem(one_chip):
    """Mosaic refuses the stem's strided value slice; the wrapper says so first."""
    x = _sds((1, 224, 224, 3), one_chip)
    w = _sds((7, 7, 3, 64), one_chip)
    with pytest.raises(ValueError, match="stride 2"):
        jax.jit(functools.partial(ops.conv2d_im2col, stride=2)).lower(x, w)


@functools.lru_cache(maxsize=None)
def compiled_resnet50_runner(topo, n_stages):
    """The full-width 224x224 PipelineRunner program, 8 microbatches of 1
    image, compiled once for each stage count."""
    model = make_cnn("resnet50")
    in_shape = (224, 224, 3)
    platform = tpu_platform_from_mesh(4, chips_per_stage=1)
    conf = generate_seed(weights(network_layers("resnet50")), platform, n_stages=n_stages).conf
    mesh = Mesh(np.asarray(topo.devices[:n_stages]).reshape(n_stages, 1), ("stage", "inner"))
    replicated = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
    )
    _, _, _, canon = canonical_pipeline_apply(model, params, in_shape)
    micro = _sds((8, 1, *canon), replicated)

    def program(params, micro):
        apply_fn, *_ = canonical_pipeline_apply(model, params, in_shape)
        return PipelineRunner(mesh=mesh, conf=conf, apply_layer=apply_fn, n_micro=8).run(micro)

    with jax.default_matmul_precision("highest"):
        return jax.jit(program).lower(params, micro).compile()


def conv_input_features(text: str) -> list[int]:
    """The input feature dimension of every convolution in compiled HLO text."""
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    features = []
    for lhs, labels in re.findall(r" convolution\(%([\w.\-]+), .*?dim_labels=(\w+)_", text):
        dims = [int(d) for d in shapes[lhs].split(",")]
        features.append(dims[labels.index("f")])
    return features


@pytest.mark.parametrize("n_stages", [1, 4])
def test_resnet50_pipeline_fits_one_chip(topo, n_stages):
    """The full-width 224x224 PipelineRunner program, 8 microbatches of 1 image.

    Its buffers hold each image as the flat canonical, so the program is about
    the weights (91 MB) and 8 of ResNet-50's largest activations (3.2 MB each)
    in and out.  Inside a stage each layer's pad meets the next layer's slice
    and folds away: a stage keeps at most four of the canonical's pads (its
    output's, to whole rows and to all rows, and copies the compiler makes
    of them or merges with the first conv's padding), not two a layer.
    """
    compiled = compiled_resnet50_runner(topo, n_stages)
    mem = compiled.memory_analysis()
    per_device = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert per_device < RUNNER_BYTES, (n_stages, per_device)
    text = compiled.as_text()
    assert len(re.findall(r"= \S+ pad\(.*shisha\.canon", text)) <= 4 * n_stages
    if n_stages > 1:
        assert "collective-permute" in text


def test_resnet50_runner_has_no_lane_sparse_conv(topo):
    """The stem's 3 input channels go into one matmul's contraction with its
    taps (252 rows: 7 tap rows of 12 pixels by 3 channels, for 2 output
    pixels a row): no convolution of the 1-stage program reads 3 features,
    and the program still fits."""
    compiled = compiled_resnet50_runner(topo, 1)
    features = conv_input_features(compiled.as_text())
    assert len(features) >= 50 and 3 not in features, sorted(set(features))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes < RUNNER_BYTES
