"""Plain float32 reference of a configuration's conv chain.

Written from the configuration's layer table alone, in ``jax.numpy``, and
independent of the program: every layer is a "SAME" convolution taken as a
sum of shifted matrix products, plus bias, then ReLU.  Where a layer's input
grid (``h_out * stride``, ``c_in``) differs from what the previous layer gave,
the activation is first brought onto it by a nearest resize with half-pixel
centres: output index ``i`` of ``n`` reads input index ``floor((i + 0.5) * m
/ n)`` of ``m``, in every dimension that changes.

``precision="highest"`` is the configuration's own: float32 products, as on
a TPU under ``jax.default_matmul_precision("highest")``.  ``precision="high"``
is the control one step below it: each product is taken in three bfloat16
passes (high x high + high x low + low x high), the TPU's "high", emulated
here so that it reads the same on any backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


def layer_rows(config: dict) -> list[dict]:
    """The layer table as one dict per layer, keyed by ``layer_columns``."""
    cols = config["layer_columns"]
    return [dict(zip(cols, row)) for row in config["layers"]]


def _nearest_index(m: int, n: int) -> np.ndarray:
    return ((2 * np.arange(n) + 1) * m) // (2 * n)


def nearest(x: jax.Array, h: int, w: int, c: int) -> jax.Array:
    """Nearest resize of an NHWC array onto (h, w, c), half-pixel centres."""
    for axis, n in ((1, h), (2, w), (3, c)):
        m = x.shape[axis]
        if m != n:
            x = jnp.take(x, _nearest_index(m, n), axis=axis)
    return x


def _bf16_part(a: jax.Array) -> jax.Array:
    """Each float32 rounded to the nearest bfloat16 (ties to even), held in
    float32.  Bit arithmetic, not a pair of casts, which a compiler may fold
    away; finite inputs only."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split_bf16(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    hi = _bf16_part(a)
    return hi, _bf16_part(a - hi)


def _matmul(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    ein = functools.partial(jnp.einsum, "nhwc,ck->nhwk", precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return ein(a, b)
    if precision == "high":
        a_hi, a_lo = _split_bf16(a)
        b_hi, b_lo = _split_bf16(b)
        return ein(a_hi, b_hi) + ein(a_hi, b_lo) + ein(a_lo, b_hi)
    raise ValueError(f"unknown precision {precision!r}; known: {PRECISIONS}")


def conv_same(x: jax.Array, w: jax.Array, stride: int, precision: str = "highest") -> jax.Array:
    """'SAME' convolution, NHWC input, HWIO weights, as shifted matmuls."""
    _, h, wd, _ = x.shape
    r, s, _, _ = w.shape
    ho, wo = -(-h // stride), -(-wd // stride)
    ph = max((ho - 1) * stride + r - h, 0)
    pw = max((wo - 1) * stride + s - wd, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2), (0, 0)))
    y = None
    for dr in range(r):
        for ds in range(s):
            patch = xp[:, dr : dr + (ho - 1) * stride + 1 : stride, ds : ds + (wo - 1) * stride + 1 : stride, :]
            term = _matmul(patch, w[dr, ds], precision)
            y = term if y is None else y + term
    return y


def layer(row: dict, p: dict, x: jax.Array, precision: str = "highest") -> jax.Array:
    """One layer: resize onto its input grid if needed, conv, bias, ReLU."""
    x = nearest(x, row["h_out"] * row["stride"], row["w_out"] * row["stride"], row["c_in"])
    return jnp.maximum(conv_same(x, p["w"], row["stride"], precision) + p["b"], 0.0)


def forward(rows: list[dict], params, x: jax.Array, precision: str = "highest") -> jax.Array:
    for row, p in zip(rows, params):
        x = layer(row, p, x, precision)
    return x


def make_forward(rows: list[dict], precision: str = "highest"):
    """The whole chain as one jitted program of (params, images)."""
    return jax.jit(functools.partial(forward, rows, precision=precision))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want| over max |want|: the widest gap, on the output's own scale."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want))) / scale if scale > 0 else float("inf")
