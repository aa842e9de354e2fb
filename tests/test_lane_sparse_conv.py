"""Lane-sparse convs as one matmul over their patch matrix.

A conv over fewer than ``LANE_SPARSE_CHANNELS`` input channels (the first
layer of every network here) runs as one ``dot_general`` with every tap in
the contraction, its patch matrix built under the ``shisha.patches`` scope.
These tests hold it to ``lax.conv_general_dilated`` at "highest" on odd and
even grids, and pin which layers the shape rule picks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.cnn import (
    LANE_SPARSE_CHANNELS,
    LEAKY_SLOPE,
    NETWORKS,
    PATCHES_SCOPE,
    CNNModel,
    lane_packed,
    make_cnn,
)

# (network, h_out): ResNet-50's 7x7/2 "SAME" stem, YOLOv3's 3x3/1 conv0 with
# Darknet's r//2 padding and leaky ReLU, AlexNet's 11x11/4 "SAME" conv1
FIRST_LAYERS = [
    ("resnet50", 5),
    ("resnet50", 6),
    ("yolov3_det", 7),
    ("yolov3_det", 8),
    ("alexnet", 3),
    ("alexnet", 4),
]


def conv_layer(sp, p, x):
    """The layer as ``lax.conv_general_dilated``, bias and activation."""
    padding = {"same": "SAME", "darknet": [(sp.r // 2, sp.r // 2), (sp.s // 2, sp.s // 2)]}[sp.pad]
    y = jax.lax.conv_general_dilated(
        x, p["w"], (sp.stride, sp.stride), padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    y = y + p["b"]
    return jax.nn.relu(y) if sp.act == "relu" else jax.nn.leaky_relu(y, LEAKY_SLOPE)


@pytest.mark.parametrize("net,h_out", FIRST_LAYERS)
def test_patch_matmul_matches_conv(net, h_out):
    sp = dataclasses.replace(NETWORKS[net]()[0], h_out=h_out, w_out=h_out)
    assert lane_packed(sp)
    model = CNNModel(specs=(sp,))
    key_w, key_b, key_x = jax.random.split(jax.random.PRNGKey(h_out), 3)
    p = model.init(key_w)[0]
    p["b"] = 0.1 * jax.random.normal(key_b, (sp.k,))
    in_hw = h_out * sp.stride
    x = jax.random.normal(key_x, (2, in_hw, in_hw, sp.c_in), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply_layer, static_argnums=0)(0, p, x))
        want = np.asarray(jax.jit(conv_layer, static_argnums=0)(sp, p, x))
    assert got.shape == want.shape == (2, h_out, h_out, sp.k)
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


@pytest.mark.parametrize("scale", [1.0, 0.05])
@pytest.mark.parametrize("net", sorted(NETWORKS))
def test_lane_packed_is_each_networks_first_layer(net, scale):
    specs = make_cnn(net, scale=scale).specs
    assert [i for i, sp in enumerate(specs) if lane_packed(sp)] == [0]
    assert specs[0].c_in == 3 and min(sp.c_in for sp in specs[1:]) >= LANE_SPARSE_CHANNELS


@pytest.mark.parametrize("layer,packed", [(0, True), (2, False)])
def test_patches_carry_their_scope(layer, packed):
    """The stem builds its patches under ``shisha.patches`` and has no conv;
    a layer over 8 channels (the fewest that keep the conv) has no patches."""
    sp = make_cnn("resnet50", scale=0.05).specs[layer]
    model = CNNModel(specs=(sp,))
    p = model.init(jax.random.PRNGKey(0))[0]
    x = jnp.zeros((1, sp.h_out * sp.stride, sp.w_out * sp.stride, sp.c_in), jnp.float32)
    text = jax.jit(model.apply_layer, static_argnums=0).lower(0, p, x).compile().as_text()
    assert (PATCHES_SCOPE in text) == packed
    assert ("conv_general_dilated" in text) != packed
