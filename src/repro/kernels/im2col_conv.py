"""Im2Col + GEMM convolution Pallas kernel.

This is the GEMM-based conv operator the paper simulates in gem5 (§6:
"A GEMM-based implementation consists of two operators: Im2Col and GEMM"),
adapted to the TPU memory hierarchy: instead of materializing the
[HO·WO, R·S·C] patch matrix in HBM (the CPU/gem5 formulation), the kernel
accumulates R·S shifted [HO·WO, C] × [C, K] matmuls out of VMEM — an
implicit-GEMM layout that keeps the patch matrix entirely virtual and the
MXU fed with C/K-contiguous panels.

Tiling: grid (N, K/BK).  One image (padded, NHWC) is resident in VMEM per
step; output channels are swept in BK=128 MXU-aligned slices.  This covers
the paper's CNN layers (≤416² activations) within VMEM; larger frontends
would add an H-halo grid dimension — noted in DESIGN.md, not needed for
the assigned workloads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv_kernel(x_ref, w_ref, o_ref, *, r: int, s: int, stride: int, ho: int, wo: int):
    x = x_ref[0]  # [HP, WP, C] padded input, VMEM-resident
    c = x.shape[-1]
    acc = jnp.zeros((ho * wo, o_ref.shape[-1]), jnp.float32)
    for dr in range(r):  # unrolled R·S implicit-GEMM accumulation
        for ds in range(s):
            patch = jax.lax.slice(
                x,
                (dr, ds, 0),
                (dr + (ho - 1) * stride + 1, ds + (wo - 1) * stride + 1, c),
                (stride, stride, 1),
            )  # [HO, WO, C]
            acc += jnp.dot(
                patch.reshape(ho * wo, c),
                w_ref[dr, ds],
                preferred_element_type=jnp.float32,
            )
    o_ref[0] = acc.reshape(ho, wo, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "bk", "interpret"))
def conv2d_im2col(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """SAME-padded conv. x: [N, H, W, C]; w: [R, S, C, K] -> [N, HO, WO, K].

    Mosaic refuses the strided VMEM value slice of ``stride > 1``
    (``vector.extract_strided_slice`` takes unit strides only), so a strided
    conv runs only in interpret mode.
    """
    if stride > 1 and not interpret:
        raise ValueError(f"conv2d_im2col: stride {stride} > 1 does not compile for the TPU; use interpret=True")
    n, h, wid, c = x.shape
    r, s, c2, k = w.shape
    assert c == c2, (x.shape, w.shape)
    ho, wo = -(-h // stride), -(-wid // stride)
    pad_h = max((ho - 1) * stride + r - h, 0)
    pad_w = max((wo - 1) * stride + s - wid, 0)
    xp = jnp.pad(x, ((0, 0), (pad_h // 2, pad_h - pad_h // 2), (pad_w // 2, pad_w - pad_w // 2), (0, 0)))
    bk = min(bk, k)
    kp = -(-k // bk) * bk
    if kp != k:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, kp - k)))
    hp, wp = xp.shape[1], xp.shape[2]
    out = pl.pallas_call(
        functools.partial(_conv_kernel, r=r, s=s, stride=stride, ho=ho, wo=wo),
        grid=(n, kp // bk),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((r, s, c, bk), lambda i, j: (0, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, ho, wo, bk), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, kp), x.dtype),
        interpret=interpret,
    )(xp, w)
    return out[..., :k]
