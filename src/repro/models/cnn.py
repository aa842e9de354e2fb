"""The paper's CNN workloads: ResNet50, YOLOv3 (Darknet-53), AlexNet, SynthNet,
and YOLOv3 as published (``yolov3_det``: Darknet-53 with its shortcut adds and
the three-scale detection heads).

Two views of each network:

  1. ``*_layers()`` — the per-layer Eq.-1 cost tables the scheduler consumes
     (the paper's "50 compute intensive layers in ResNet50 / 52 in YOLOv3",
     §7.1).  These drive the faithful-reproduction benchmarks.
  2. ``CNNModel`` — a runnable JAX network built from the same table, used
     by the pipeline runtime and the live-measured oracle.  Every layer is
     an XLA conv, except a lane-sparse one (fewer than 8 input channels:
     each network's first), which runs as one matmul over its patch matrix.

SynthNet is the paper's synthetic 18-layer network: AlexNet's five conv
layers replicated (channels chained across repeats) to reach 18 layers.

Every network but ``yolov3_det`` is a chain: each layer reads its
predecessor's output.  ``yolov3_det`` is a layer graph (``ConvSpec.srcs``,
``add``, ``up``): a tensor may stay live across many layers before a route
or a shortcut reads it, so a stage boundary carries a *live set* of tensors.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cost_model import Layer, conv_layer


# the device scope of a layer's work on tensors other than its own
# predecessor's output: route concats, shortcut adds, upsamples, and the
# slots of a live set in the pipeline's canonical
SKIP_SCOPE = "shisha.skip"
LEAKY_SLOPE = 0.1  # Darknet's leaky ReLU
INPUT = -1  # the tensor index of the network's input
# a TPU tiles the two minor dims of an array (8, 128): an image held as rows
# of 128 lanes is padded and sliced in whole rows
CANON_LANES = 128
# the device scope of a lane-packed layer's patch matrix
PATCHES_SCOPE = "shisha.patches"
# a conv over fewer input channels than this leaves the TPU's 128 lanes nearly
# empty (and each tap's contraction as shallow): it runs as one matmul with
# every tap in the contraction
LANE_SPARSE_CHANNELS = 8


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """One conv layer.  The last five fields make a layer graph, as Darknet's
    cfg does; their defaults are the chain's layer."""

    name: str
    h_out: int
    w_out: int
    c_in: int
    r: int
    s: int
    k: int
    stride: int = 1
    srcs: tuple[int, ...] | None = None  # layers whose outputs are concatenated (route); None: the predecessor
    add: int | None = None  # layer whose output is added after the activation (shortcut)
    up: int = 1  # nearest upsample factor of the output
    act: str = "relu"  # "relu", "leaky" or "linear"
    pad: str = "same"  # "same", or "darknet": r // 2 on every side


def sources(specs: Sequence[ConvSpec], i: int) -> tuple[int, ...]:
    """The tensors layer i concatenates, in route order (INPUT: the network's input)."""
    return specs[i].srcs if specs[i].srcs is not None else (i - 1,)


def reads(specs: Sequence[ConvSpec], i: int) -> tuple[int, ...]:
    """Every tensor layer i reads: its sources, then its shortcut's."""
    add = specs[i].add
    return sources(specs, i) + (() if add is None else (add,))


def out_shape(sp: ConvSpec) -> tuple[int, int, int]:
    """A layer's output tensor (h, w, c), after its upsample."""
    return (sp.h_out * sp.up, sp.w_out * sp.up, sp.k)


def tensor_shape(specs: Sequence[ConvSpec], t: int) -> tuple[int, int, int]:
    """Tensor t's (h, w, c); the input is layer 0's input grid."""
    if t == INPUT:
        sp = specs[0]
        return (sp.h_out * sp.stride, sp.w_out * sp.stride, sp.c_in)
    return out_shape(specs[t])


def outputs(specs: Sequence[ConvSpec]) -> list[int]:
    """The network's outputs: the tensors no layer reads (of no layers, the input)."""
    read = {t for i in range(len(specs)) for t in reads(specs, i)}
    return [t for t in range(INPUT, len(specs)) if t not in read]


def live_sets(specs: Sequence[ConvSpec]) -> list[list[int]]:
    """The tensors live on each stage boundary, in the order they were made.

    Boundary b is what enters layer b (b = len(specs): the outputs).  A
    tensor is live from the boundary after the layer that makes it to the
    boundary of its last reader; an output, to the last boundary.  For a
    chain each boundary holds one tensor: the previous layer's output.
    """
    n = len(specs)
    last = {t: n for t in outputs(specs)}
    for i in range(n):
        for t in reads(specs, i):
            last[t] = max(last.get(t, i), i)
    return [[t for t in range(INPUT, n) if t + 1 <= b <= last[t]] for b in range(n + 1)]


def lane_packed(sp: ConvSpec) -> bool:
    """Whether a layer runs as one matmul over its patch matrix: a conv whose
    few input channels would leave the lanes nearly empty (in every network
    here, its first layer)."""
    return sp.c_in < LANE_SPARSE_CHANNELS


def _to_layers(specs: Sequence[ConvSpec]) -> list[Layer]:
    """Eq.-1 costs; a layer's ``act_bytes`` is the live set on the boundary
    after it (for a chain, its output)."""
    live = live_sets(specs)
    layers = []
    for i, sp in enumerate(specs):
        layer = conv_layer(sp.name, sp.h_out, sp.w_out, sp.c_in, sp.r, sp.s, sp.k)
        live_bytes = 4 * sum(math.prod(tensor_shape(specs, t)) for t in live[i + 1])
        layers.append(dataclasses.replace(layer, act_bytes=live_bytes))
    return layers


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
# YOLOv3 as published — Darknet's cfg/yolov3.cfg, layers 0-106 @416²: the
# backbone above with its 23 shortcut adds, then three detection heads.
# 75 convs; YOLO layers (box decode) are host post-processing and not here.
# ---------------------------------------------------------------------------


def yolov3_det_specs() -> list[ConvSpec]:
    specs: list[ConvSpec] = []

    def conv(name, hw, c_in, r, k, stride=1, srcs=None, add=None, up=1, act="leaky"):
        specs.append(ConvSpec(name, hw, hw, c_in, r, r, k, stride, srcs, add, up, act, "darknet"))
        return len(specs) - 1

    last = conv("conv0", 416, 3, 3, 32)
    c_in, taps = 32, {}
    for hw, ch, n_res in [(208, 64, 1), (104, 128, 2), (52, 256, 8), (26, 512, 8), (13, 1024, 4)]:
        last = conv(f"down{ch}", hw, c_in, 3, ch, stride=2)
        c_in = ch
        for b in range(n_res):
            block_in = last
            conv(f"res{ch}_{b}_1x1", hw, ch, 1, ch // 2)
            last = conv(f"res{ch}_{b}_3x3", hw, ch // 2, 3, ch, add=block_in)
        taps[hw] = last  # Darknet layers 36 (52x52x256), 61 (26x26x512), 74
    assert len(specs) == 52

    route, c_route = None, 0  # the upsampled branch the next head concatenates
    for hw, ch in [(13, 512), (26, 256), (52, 128)]:
        srcs = None if route is None else (route, taps[hw])
        c_in = c_route + (1024 if route is None else 2 * ch)
        for j in range(6):  # 1x1 and 3x3 in turn, Darknet's layers 75-80
            conv(f"h{hw}_{j}", hw, c_in, 1 + 2 * (j % 2), ch * (1 + j % 2), srcs=srcs)
            srcs, c_in = None, ch * (1 + j % 2)
        conv(f"h{hw}_out", hw, 2 * ch, 1, 255, act="linear")
        if hw != 52:  # route -4 (h*_4), 1x1, upsample x2
            route = conv(f"h{hw}_up", hw, ch, 1, ch // 2, srcs=(len(specs) - 3,), up=2)
            c_route = ch // 2
    assert len(specs) == 75
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
    "yolov3_det": yolov3_det_specs,
    "alexnet": alexnet_specs,
    "synthnet": synthnet_specs,
}


def network_layers(name: str) -> list[Layer]:
    """Per-layer Eq.-1 cost table for a paper network."""
    return _to_layers(NETWORKS[name]())


# ---------------------------------------------------------------------------
# Runnable JAX CNN built from the same spec table
# ---------------------------------------------------------------------------


def _activate(y: jax.Array, sp: ConvSpec) -> jax.Array:
    if sp.act == "relu":
        return jax.nn.relu(y)
    if sp.act == "leaky":
        return jax.nn.leaky_relu(y, LEAKY_SLOPE)
    if sp.act != "linear":
        raise ValueError(f"{sp.name}: unknown activation {sp.act!r}")
    return y


def patch_matmul(x: jax.Array, p: dict[str, jax.Array], sp: ConvSpec, padding) -> jax.Array:
    """A lane-packed layer, bias and activation, as one matmul over its patch
    matrix, with every tap and channel in the contraction.

    One row of the patch matrix serves ``g`` adjacent output pixels (as many
    as fill 128 lanes with their k channels and divide w_out), so that the
    matmul's output fills the lanes: its weight holds the layer's (r, s, c,
    k) weight once for each of the g pixels, shifted by the stride and zero
    elsewhere in the window of ``nb`` blocks of ``u = stride * g`` input
    pixels.  The matrix is built under the ``shisha.patches`` scope, without
    a strided slice: ``stride`` adjacent image rows go side by side in one
    long row, which is transposed once so that the output's rows lie in the
    lanes; each tap row ``dr`` is then a contiguous slice of blocks and rows.
    """
    st, ho, c, k = sp.stride, sp.h_out, sp.c_in, sp.k
    g = max(1, CANON_LANES // k)
    while sp.w_out % g:
        g -= 1
    wq, u = sp.w_out // g, st * g
    nb = -(-(st * (g - 1) + sp.s) // u)
    b, h, w = x.shape[:3]
    if padding == "SAME":
        padding = jax.lax.padtype_to_pads((h, w), (sp.r, sp.s), (st, st), "SAME")
    (top, _), (left, _) = padding
    hh, nw = (sp.r - 1) // st + ho, wq + nb - 1  # rows of st image rows, blocks of an image row
    with jax.named_scope(PATCHES_SCOPE):
        xf = jax.lax.pad(
            x.reshape(b, h, w * c),
            jnp.zeros((), x.dtype),
            [(0, 0, 0), (top, st * hh - h - top, 0), (left * c, (u * nw - w - left) * c, 0)],
        )
        # [u*c, B, st*nw, hh]: (block pixel, channel), image, (row parity, block), row pair
        xt = xf.reshape(b, hh, st * nw * u * c).transpose(0, 2, 1).reshape(b, st * nw, u * c, hh).transpose(2, 0, 1, 3)
        rows = [
            xt[:, :, (dr % st) * nw + j : (dr % st) * nw + j + wq, dr // st : dr // st + ho].reshape(u * c, -1)
            for dr in range(sp.r)
            for j in range(nb)
        ]
        patches = jnp.concatenate(rows, axis=0)  # [r*nb*u*c, B*wq*ho]
    # output pixel q of a group reads window pixels st*q ... st*q + s - 1
    blocks = []
    for q in range(g):
        before = jnp.zeros((sp.r, st * q, c, k), x.dtype)
        after = jnp.zeros((sp.r, nb * u - sp.s - st * q, c, k), x.dtype)
        blocks.append(jnp.concatenate([before, p["w"], after], axis=1))
    # where the weight is a constant, XLA would split a matmul over a
    # concatenation into one small matmul per tap block; the barrier keeps it one
    wg = jax.lax.optimization_barrier(jnp.stack(blocks, axis=-2).reshape(-1, g * k))
    y = jax.lax.dot_general(patches, wg, (((0,), (0,)), ((), ())))
    y = _activate(y + jnp.tile(p["b"], g), sp)
    return y.reshape(b, wq, ho, g * k).transpose(0, 2, 1, 3).reshape(b, ho, sp.w_out, k)


@dataclasses.dataclass(frozen=True)
class CNNModel:
    """A runnable conv network (inference) matching a spec table.

    In a chain, spatial dims are synthetic (every layer runs at its table
    resolution via resize), which keeps the chain runnable layer-by-layer —
    exactly what the pipeline runtime needs: each stage applies its own
    contiguous slice.  A layer graph's grids line up as published.
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

    def apply_layer(self, i: int, p: dict[str, jax.Array], *xs: jax.Array) -> jax.Array:
        """Layer i on the tensors it ``reads``: its sources, then its shortcut's.

        Route concats, the shortcut add and the output's upsample carry the
        ``shisha.skip`` named scope.
        """
        sp = self.specs[i]
        n_src = len(sources(self.specs, i))
        x = xs[0]
        if n_src > 1:
            with jax.named_scope(SKIP_SCOPE):
                x = jnp.concatenate(xs[:n_src], axis=-1)
        # bring x to this layer's expected input grid
        in_h = sp.h_out * sp.stride
        if x.shape[1] != in_h or x.shape[3] != sp.c_in:
            x = jax.image.resize(x, (x.shape[0], in_h, in_h, sp.c_in), "nearest")
        padding = {"same": "SAME", "darknet": [(sp.r // 2, sp.r // 2), (sp.s // 2, sp.s // 2)]}[sp.pad]
        if lane_packed(sp):
            y = patch_matmul(x, p, sp, padding)
        else:
            y = jax.lax.conv_general_dilated(
                x,
                p["w"],
                window_strides=(sp.stride, sp.stride),
                padding=padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            y = _activate(y + p["b"], sp)
        if sp.add is not None:
            with jax.named_scope(SKIP_SCOPE):
                y = y + xs[n_src]
        if sp.up > 1:
            with jax.named_scope(SKIP_SCOPE):
                y = jnp.repeat(jnp.repeat(y, sp.up, axis=1), sp.up, axis=2)
        return y

    def __call__(self, params, x: jax.Array):
        """The whole network: its one output, or a tuple of its outputs."""
        specs = self.specs
        live = live_sets(specs)
        acts = {INPUT: x}
        for i in range(len(specs)):
            acts[i] = self.apply_layer(i, params[i], *(acts[t] for t in reads(specs, i)))
            acts = {t: acts[t] for t in live[i + 1]}
        outs = tuple(acts[t] for t in outputs(specs))
        return outs[0] if len(outs) == 1 else outs


def _rows(n: int) -> int:
    return -(-n // CANON_LANES)


def live_slots(specs: Sequence[ConvSpec], input_shape: tuple[int, int, int]) -> dict[int, tuple[int, int]]:
    """Each tensor's fixed slot (first row, rows) of whole 128-lane rows for
    its lifetime: first fit, in the order the tensors are made, among the
    tensors live on a boundary with it."""
    live = live_sets(specs)
    span = {t: [b for b, ts in enumerate(live) if t in ts] for t in range(INPUT, len(specs))}
    slots: dict = {}
    for t in span:
        taken = sorted(slots[u] for u in slots if span[u][0] <= span[t][-1] and span[t][0] <= span[u][-1])
        shape = input_shape if t == INPUT else out_shape(specs[t])
        need, at = _rows(math.prod(shape)), 0
        for a, n in taken:
            if at + need <= a:
                break
            at = max(at, a + n)
        slots[t] = (at, need)
    return slots


def canonical_pipeline_apply(model: CNNModel, params, input_shape: tuple[int, int, int]):
    """Shape-uniform layer application for the stage pipeline.

    Pipeline stages must be branch-compatible under lax.switch, so every
    layer maps one canonical activation to itself: [B, R, 128] rows of 128
    lanes per image, holding the *live set* of the boundary — every tensor
    made by an earlier layer that this or a later layer still reads (for a
    chain, the previous layer's output alone).  Each tensor has a fixed slot
    of whole rows for its lifetime, first fit among the tensors live on a
    boundary with it, and holds its image flattened in row-major (h, w, c)
    order, zero-padded to whole rows.  ``R`` is the largest slot end; for a
    chain every slot starts at row 0, so ``R * 128`` is the largest element
    count of any activation on a boundary, rounded up to 128.

    Layer i slices the tensors it reads out of their slots, computes, and
    writes its output into its slot: where the boundary after it holds that
    output alone, as a whole-row pad of the canonical (which drops the
    tensors nobody reads any more), otherwise in place, beside the tensors
    still live.  The pipelined result is bit-identical to sequential
    execution.

    Slot reads and writes of a tensor that lives on one boundary, and the
    final crop, carry the ``shisha.canon`` named scope; those of a tensor
    kept across several boundaries for a later layer carry ``shisha.skip``
    (as do concats, adds and upsamples), inside whatever scope the caller
    wraps a layer in.

    Returns (apply_fn, to_canon, crop_out, canon_shape): ``to_canon`` takes
    the input [B, h, w, c], ``crop_out`` takes [..., R, 128] and gives the
    network's output [..., h, w, k] (a tuple of them where it has several),
    and ``canon_shape`` is (R, 128).
    """
    specs = model.specs
    live = live_sets(specs)
    shapes = {INPUT: tuple(input_shape)} | {i: out_shape(sp) for i, sp in enumerate(specs)}
    slots = live_slots(specs, input_shape)
    rows = max(a + n for a, n in slots.values())
    canon = (rows, CANON_LANES)
    boundaries = collections.Counter(t for ts in live for t in ts)

    def scope(t):
        return jax.named_scope("shisha.canon" if boundaries[t] == 1 else SKIP_SCOPE)

    # Both directions move whole 128-lane rows: padding or slicing the flat
    # image at its full length lets the TPU compiler lay the flat dim out
    # major, which makes the copy of a lane-sparse input (c = 3) far slower.
    def to_rows(x):
        b, n = x.shape[0], x[0].size
        flat = jnp.pad(x.reshape(b, n), ((0, 0), (0, _rows(n) * CANON_LANES - n)))
        return flat.reshape(b, -1, CANON_LANES)

    def write(xc, t, y, alone):
        at = slots[t][0]
        with scope(t):
            y = to_rows(y)
            if alone:
                return jnp.pad(y, ((0, 0), (at, rows - at - y.shape[1]), (0, 0)))
            return jax.lax.dynamic_update_slice_in_dim(xc, y, at, axis=-2)

    def take(xc, t):
        at, r = slots[t]
        shape = shapes[t]
        lead = xc.shape[:-2]
        return xc[..., at : at + r, :].reshape(*lead, r * CANON_LANES)[..., : math.prod(shape)].reshape(*lead, *shape)

    def to_canon(x):
        return write(None, INPUT, x, alone=True)

    def apply_fn(i, xc):
        xs = []
        for t in reads(specs, i):
            with scope(t):
                xs.append(take(xc, t))
        y = model.apply_layer(i, params[i], *xs)
        return write(xc, i, y, len(live[i + 1]) == 1)

    def crop_out(xc):
        with jax.named_scope("shisha.canon"):
            outs = tuple(take(xc, t) for t in live[-1])
        return outs[0] if len(outs) == 1 else outs

    return apply_fn, to_canon, crop_out, canon


def make_cnn(name: str, scale: float = 1.0) -> CNNModel:
    """Runnable model; ``scale`` shrinks channels for CPU smoke tests.

    A chain's layers each shrink their grid too.  A layer graph keeps every
    stride, upsample and concat aligned: each grid is divided by one factor,
    which takes its smallest grid to ``max(2, int(smallest * scale))``, and
    a concat's ``c_in`` is the sum of its sources' channels.
    """
    specs = NETWORKS[name]()
    if scale == 1.0:
        return CNNModel(specs=tuple(specs))
    graph = any(sp.srcs is not None or sp.add is not None or sp.up != 1 for sp in specs)
    smallest = min(sp.h_out for sp in specs)
    scaled: list[ConvSpec] = []
    for i, sp in enumerate(specs):
        k = max(8, int(sp.k * scale))
        if graph:
            h = sp.h_out * max(2, int(smallest * scale)) // smallest
            c_in = sum(scaled[t].k if t != INPUT else sp.c_in for t in sources(specs, i))
        else:
            h = max(4, int(sp.h_out * scale))
            c_in = scaled[-1].k if scaled else sp.c_in
        scaled.append(dataclasses.replace(sp, h_out=h, w_out=h, c_in=c_in, k=k))
    return CNNModel(specs=tuple(scaled))
