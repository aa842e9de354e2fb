"""Plain float32 reference of a configuration's layer graph.

Written from the configuration alone, in ``jax.numpy``, and independent of
the program: the layer table (``layers``) and, beside it, each layer's place
in the graph (``graph``, columns ``graph_columns``), as Darknet's cfg gives
them:

- ``srcs``: the tensors whose outputs are concatenated on the channels, in
  route order, to make the layer's input (-1: the image);
- the convolution, taken as a sum of shifted matrix products over an input
  padded explicitly: ``darknet`` puts ``r // 2`` zeros on every side,
  ``same`` what "SAME" puts;
- plus bias, then the activation: ``relu``, ``leaky`` (slope 0.1) or
  ``linear``;
- ``add``: the tensor added after the activation (shortcut), or null;
- ``up``: the output is repeated ``up`` times along each spatial axis
  (nearest upsample).

The network's outputs are the tensors no layer reads, in layer order.  A
configuration without ``graph`` is a chain, and each of its layers is
``reference.layer``, nearest resize included.  ``precision`` is as in
``reference``: "highest" is the configuration's own, "high" the control one
step below it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

LEAKY_SLOPE = 0.1
INPUT = -1


def graph_rows(config: dict) -> list[dict]:
    """One dict per layer: the table's columns and the graph's (a chain's
    where the configuration has no ``graph``); ``chain`` says which."""
    rows = reference.layer_rows(config)
    if "graph" not in config:
        return [dict(r, chain=True, srcs=[i - 1], add=None, up=1, act="relu", pad="same") for i, r in enumerate(rows)]
    cols = config["graph_columns"]
    return [dict(r, chain=False, **dict(zip(cols, g))) for r, g in zip(rows, config["graph"])]


def outputs(rows: list[dict]) -> list[int]:
    read = {t for r in rows for t in r["srcs"] + ([r["add"]] if r["add"] is not None else [])}
    return [i for i in range(len(rows)) if i not in read]


def conv(x: jax.Array, w: jax.Array, stride: int, pad: str, precision: str) -> jax.Array:
    """Convolution of an NHWC input with HWIO weights, padded as ``pad`` says."""
    _, h, wd, _ = x.shape
    r, s, _, _ = w.shape
    if pad == "darknet":
        pads = [(r // 2, r // 2), (s // 2, s // 2)]
    elif pad == "same":
        pads = []
        for n, k in ((h, r), (wd, s)):
            total = max((-(-n // stride) - 1) * stride + k - n, 0)
            pads.append((total // 2, total - total // 2))
    else:
        raise ValueError(f"unknown pad {pad!r}")
    xp = jnp.pad(x, ((0, 0), *pads, (0, 0)))
    ho = (h + sum(pads[0]) - r) // stride + 1
    wo = (wd + sum(pads[1]) - s) // stride + 1
    y = None
    for dr in range(r):
        for ds in range(s):
            patch = xp[:, dr : dr + (ho - 1) * stride + 1 : stride, ds : ds + (wo - 1) * stride + 1 : stride, :]
            term = reference._matmul(patch, w[dr, ds], precision)
            y = term if y is None else y + term
    return y


def activation(y: jax.Array, act: str) -> jax.Array:
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "leaky":
        return jnp.where(y > 0, y, LEAKY_SLOPE * y)
    if act == "linear":
        return y
    raise ValueError(f"unknown activation {act!r}")


def layer(row: dict, p: dict, xs: list[jax.Array], precision: str = "highest") -> jax.Array:
    """One layer of the graph on its sources (then its shortcut's tensor)."""
    if row["chain"]:
        return reference.layer(row, p, xs[0], precision)
    n = len(row["srcs"])
    x = jnp.concatenate(xs[:n], axis=-1)
    y = activation(conv(x, p["w"], row["stride"], row["pad"], precision) + p["b"], row["act"])
    if row["add"] is not None:
        y = y + xs[n]
    if row["up"] > 1:
        y = jnp.repeat(jnp.repeat(y, row["up"], axis=1), row["up"], axis=2)
    return y


def forward(rows: list[dict], params, x: jax.Array, precision: str = "highest") -> tuple:
    """The network's outputs, as a tuple."""
    acts = {INPUT: x}
    for i, (row, p) in enumerate(zip(rows, params)):
        reads = row["srcs"] + ([row["add"]] if row["add"] is not None else [])
        acts[i] = layer(row, p, [acts[t] for t in reads], precision)
    return tuple(acts[i] for i in outputs(rows))


def make_forward(rows: list[dict], precision: str = "highest"):
    """The whole graph as one jitted program of (params, images)."""
    return jax.jit(functools.partial(forward, rows, precision=precision))


def rel_err(got: tuple, want: tuple) -> float:
    """The widest of ``reference.rel_err`` over the outputs."""
    if len(got) != len(want):
        return float("inf")
    return max(reference.rel_err(np.asarray(g), np.asarray(w)) for g, w in zip(got, want))
