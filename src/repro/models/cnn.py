"""The paper's CNN workloads: ResNet50, YOLOv3 (Darknet-53), AlexNet, SynthNet.

Two views of each network:

  1. ``*_layers()`` — the per-layer Eq.-1 cost tables the scheduler consumes
     (the paper's "50 compute intensive layers in ResNet50 / 52 in YOLOv3",
     §7.1).  These drive the faithful-reproduction benchmarks.
  2. ``CNNModel`` — a runnable JAX network built from the same table
     (Im2Col+GEMM conv operator, optionally through the Pallas kernel),
     used by the pipeline-inference example and the live-measured oracle.

SynthNet is the paper's synthetic 18-layer network: AlexNet's five conv
layers replicated (channels chained across repeats) to reach 18 layers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cost_model import Layer, conv_layer


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    name: str
    h_out: int
    w_out: int
    c_in: int
    r: int
    s: int
    k: int
    stride: int = 1


def _to_layers(specs: Sequence[ConvSpec]) -> list[Layer]:
    return [
        conv_layer(sp.name, sp.h_out, sp.w_out, sp.c_in, sp.r, sp.s, sp.k)
        for sp in specs
    ]


# ---------------------------------------------------------------------------
# ResNet50 — 50 compute-intensive layers (stem + 16 bottlenecks×3 + fc)
# ---------------------------------------------------------------------------


def resnet50_specs() -> list[ConvSpec]:
    specs = [ConvSpec("stem", 112, 112, 3, 7, 7, 64, stride=2)]
    stage_cfg = [  # (spatial, n_blocks, mid_channels, out_channels)
        (56, 3, 64, 256),
        (28, 4, 128, 512),
        (14, 6, 256, 1024),
        (7, 3, 512, 2048),
    ]
    c_in = 64  # after stem maxpool
    for si, (hw, n_blocks, mid, out) in enumerate(stage_cfg):
        for b in range(n_blocks):
            p = f"s{si + 1}b{b + 1}"
            specs.append(ConvSpec(f"{p}_1x1a", hw, hw, c_in, 1, 1, mid))
            specs.append(ConvSpec(f"{p}_3x3", hw, hw, mid, 3, 3, mid))
            specs.append(ConvSpec(f"{p}_1x1b", hw, hw, mid, 1, 1, out))
            c_in = out
    specs.append(ConvSpec("fc", 1, 1, 2048, 1, 1, 1000))
    assert len(specs) == 50
    return specs


# ---------------------------------------------------------------------------
# YOLOv3 backbone (Darknet-53) — 52 compute-intensive conv layers @416²
# ---------------------------------------------------------------------------


def yolov3_specs() -> list[ConvSpec]:
    specs = [ConvSpec("conv0", 416, 416, 3, 3, 3, 32)]
    c_in = 32
    plan = [  # (spatial after downsample, out_channels, n_residual_blocks)
        (208, 64, 1),
        (104, 128, 2),
        (52, 256, 8),
        (26, 512, 8),
        (13, 1024, 4),
    ]
    for hw, ch, n_res in plan:
        specs.append(ConvSpec(f"down{ch}", hw, hw, c_in, 3, 3, ch, stride=2))
        c_in = ch
        for b in range(n_res):
            specs.append(ConvSpec(f"res{ch}_{b}_1x1", hw, hw, ch, 1, 1, ch // 2))
            specs.append(ConvSpec(f"res{ch}_{b}_3x3", hw, hw, ch // 2, 3, 3, ch))
    assert len(specs) == 52
    return specs


# ---------------------------------------------------------------------------
# AlexNet convs + SynthNet (paper §7.1: AlexNet convs replicated to 18)
# ---------------------------------------------------------------------------


def alexnet_specs(c_in: int = 3, tag: str = "") -> list[ConvSpec]:
    return [
        ConvSpec(f"a{tag}conv1", 55, 55, c_in, 11, 11, 96, stride=4),
        ConvSpec(f"a{tag}conv2", 27, 27, 96, 5, 5, 256),
        ConvSpec(f"a{tag}conv3", 13, 13, 256, 3, 3, 384),
        ConvSpec(f"a{tag}conv4", 13, 13, 384, 3, 3, 384),
        ConvSpec(f"a{tag}conv5", 13, 13, 384, 3, 3, 256),
    ]


def synthnet_specs(n_layers: int = 18) -> list[ConvSpec]:
    specs: list[ConvSpec] = []
    c_in, rep = 3, 0
    while len(specs) < n_layers:
        block = alexnet_specs(c_in, tag=f"r{rep}_")
        specs.extend(block[: n_layers - len(specs)])
        c_in = specs[-1].k
        rep += 1
    return specs


NETWORKS = {
    "resnet50": resnet50_specs,
    "yolov3": yolov3_specs,
    "alexnet": alexnet_specs,
    "synthnet": synthnet_specs,
}


def network_layers(name: str) -> list[Layer]:
    """Per-layer Eq.-1 cost table for a paper network."""
    return _to_layers(NETWORKS[name]())


# ---------------------------------------------------------------------------
# Runnable JAX CNN built from the same spec table
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CNNModel:
    """A runnable conv chain (inference) matching a spec table.

    Spatial dims are synthetic (every layer runs at its table resolution via
    resize), which keeps the chain runnable layer-by-layer — exactly what the
    pipeline runtime needs: each stage applies its own contiguous slice.
    """

    specs: tuple[ConvSpec, ...]

    def init(self, key: jax.Array) -> list[dict[str, jax.Array]]:
        params = []
        for sp in self.specs:
            key, k1 = jax.random.split(key)
            fan_in = sp.c_in * sp.r * sp.s
            w = jax.random.normal(k1, (sp.r, sp.s, sp.c_in, sp.k), jnp.float32)
            params.append({"w": w / np.sqrt(fan_in), "b": jnp.zeros((sp.k,), jnp.float32)})
        return params

    def apply_layer(self, i: int, p: dict[str, jax.Array], x: jax.Array, *, use_pallas: bool = False) -> jax.Array:
        sp = self.specs[i]
        # bring x to this layer's expected input grid
        in_h = sp.h_out * sp.stride
        if x.shape[1] != in_h or x.shape[3] != sp.c_in:
            x = jax.image.resize(x, (x.shape[0], in_h, in_h, sp.c_in), "nearest")
        if use_pallas:
            from ..kernels import ops

            y = ops.conv2d_im2col(x, p["w"], stride=sp.stride)
        else:
            y = jax.lax.conv_general_dilated(
                x,
                p["w"],
                window_strides=(sp.stride, sp.stride),
                padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        return jax.nn.relu(y + p["b"])

    def apply_range(self, params, x: jax.Array, start: int, end: int, **kw) -> jax.Array:
        for i in range(start, end):
            x = self.apply_layer(i, params[i], x, **kw)
        return x

    def __call__(self, params, x: jax.Array, **kw) -> jax.Array:
        return self.apply_range(params, x, 0, len(self.specs), **kw)


# a TPU tiles the two minor dims of an array (8, 128): an image held as rows
# of 128 lanes is padded and sliced in whole rows
CANON_LANES = 128


def _rows(n: int) -> int:
    return -(-n // CANON_LANES)


def canonical_pipeline_apply(model: CNNModel, params, input_shape: tuple[int, int, int]):
    """Shape-uniform layer application for the stage pipeline.

    Pipeline stages must be branch-compatible under lax.switch, so every
    layer maps one canonical activation to itself: an image flattened in
    row-major (h, w, c) order, zero-padded to ``R * 128`` elements and held as
    [B, R, 128].  ``R * 128`` is the largest element count of any activation
    that can sit on a stage boundary (the input and every layer's output),
    rounded up to 128.  Each layer slices its real-shaped input out of the
    front of the flat image, so the pipelined result is bit-identical to
    sequential execution.

    The pad, the slice back to a layer's real shape and the final crop
    carry the ``shisha.canon`` named scope, inside whatever scope the caller
    wraps a layer in.

    Returns (apply_fn, to_canon, crop_out, canon_shape): ``to_canon`` takes
    [B, h, w, c], ``crop_out`` takes [..., R, 128] and gives the last layer's
    [..., h, w, k], and ``canon_shape`` is (R, 128).
    """
    specs = model.specs
    # boundary[i] is the activation entering layer i; the last is the output
    boundary = [tuple(input_shape)] + [(sp.h_out, sp.w_out, sp.k) for sp in specs]
    rows = _rows(max(map(math.prod, boundary)))
    canon = (rows, CANON_LANES)

    # Both directions move whole 128-lane rows: padding or slicing the flat
    # image at its full length lets the TPU compiler lay the flat dim out
    # major, which makes the copy of a lane-sparse input (c = 3) far slower.
    def to_canon(x):
        b, n = x.shape[0], x[0].size
        r = _rows(n)
        with jax.named_scope("shisha.canon"):
            flat = jnp.pad(x.reshape(b, n), ((0, 0), (0, r * CANON_LANES - n)))
            return jnp.pad(flat.reshape(b, r, CANON_LANES), ((0, 0), (0, rows - r), (0, 0)))

    def from_canon(xc, shape):
        lead = xc.shape[:-2]
        n = math.prod(shape)
        r = _rows(n)
        return xc[..., :r, :].reshape(*lead, r * CANON_LANES)[..., :n].reshape(*lead, *shape)

    def apply_fn(i, xc):
        with jax.named_scope("shisha.canon"):
            x = from_canon(xc, boundary[i])
        y = model.apply_layer(i, params[i], x)
        return to_canon(y)

    def crop_out(xc):
        with jax.named_scope("shisha.canon"):
            return from_canon(xc, boundary[-1])

    return apply_fn, to_canon, crop_out, canon


def make_cnn(name: str, scale: float = 1.0) -> CNNModel:
    """Runnable model; ``scale`` shrinks channels for CPU smoke tests."""
    specs = NETWORKS[name]()
    if scale != 1.0:
        scaled = []
        prev_k = None
        for sp in specs:
            c_in = prev_k if prev_k is not None else sp.c_in
            k = max(8, int(sp.k * scale))
            h = max(4, int(sp.h_out * scale))
            scaled.append(dataclasses.replace(sp, h_out=h, w_out=h, c_in=c_in, k=k))
            prev_k = k
        specs = scaled
    return CNNModel(specs=tuple(specs))
