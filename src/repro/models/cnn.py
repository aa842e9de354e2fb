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


def canonical_pipeline_apply(model: CNNModel, params, input_shape: tuple[int, int, int]):
    """Shape-uniform layer application for the stage pipeline.

    Pipeline stages must be branch-compatible under lax.switch, so every
    layer maps a canonical zero-padded activation [B, Hc, Wc, Cc] to itself.
    Padding + exact cropping (never resizing through the pad) keeps the
    pipelined result bit-identical to sequential execution.

    The pad, the slice back to a layer's real shape and the final crop
    carry the ``shisha.canon`` named scope, inside whatever scope the caller
    wraps a layer in.

    Returns (apply_fn, to_canon, crop_out, canon_shape).
    """
    specs = model.specs
    hc = max([input_shape[0]] + [sp.h_out * sp.stride for sp in specs] + [sp.h_out for sp in specs])
    wc = max([input_shape[1]] + [sp.w_out * sp.stride for sp in specs] + [sp.w_out for sp in specs])
    cc = max([input_shape[2]] + [sp.c_in for sp in specs] + [sp.k for sp in specs])
    canon = (hc, wc, cc)

    def to_canon(x):
        with jax.named_scope("shisha.canon"):
            return jnp.pad(
                x,
                ((0, 0), (0, hc - x.shape[1]), (0, wc - x.shape[2]), (0, cc - x.shape[3])),
            )

    def shape_into(i):
        if i == 0:
            return input_shape
        sp = specs[i - 1]
        return (sp.h_out, sp.w_out, sp.k)

    def apply_fn(i, xc):
        h, w, c = shape_into(i)
        with jax.named_scope("shisha.canon"):
            x = xc[:, :h, :w, :c]
        y = model.apply_layer(i, params[i], x)
        return to_canon(y)

    def crop_out(xc):
        sp = specs[-1]
        with jax.named_scope("shisha.canon"):
            return xc[..., : sp.h_out, : sp.w_out, : sp.k]

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
