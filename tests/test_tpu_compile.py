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

HBM_BYTES = 16e9  # one v5e chip


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


@pytest.mark.parametrize("n_stages", [1, 4])
def test_resnet50_pipeline_fits_one_chip(topo, n_stages):
    """The full-width 224x224 PipelineRunner program, 8 microbatches of 1 image."""
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
        compiled = jax.jit(program).lower(params, micro).compile()
    mem = compiled.memory_analysis()
    per_device = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert per_device < HBM_BYTES, (n_stages, per_device)
    if n_stages > 1:
        assert "collective-permute" in compiled.as_text()
