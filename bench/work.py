"""Useful work of each conv layer, at the layer table's real shapes.

Operations and bytes are those the layer needs, not what the program runs:
the canonical zero padding of the pipeline, bubble ticks and recomputed
work do not count.  A layer reads its input on its own grid
(``h_out * stride`` square, ``c_in`` channels), its weights and bias once per
call, and writes its output; a conv is 2 operations per multiply-add.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ITEMSIZE = {"float32": 4, "bfloat16": 2}
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def flops(row: dict, batch: int = 1) -> int:
    return 2 * batch * row["h_out"] * row["w_out"] * row["c_in"] * row["r"] * row["s"] * row["k"]


def bytes_moved(row: dict, batch: int = 1, dtype: str = "float32") -> int:
    h_in, w_in = row["h_out"] * row["stride"], row["w_out"] * row["stride"]
    acts = batch * (h_in * w_in * row["c_in"] + row["h_out"] * row["w_out"] * row["k"])
    params = row["r"] * row["s"] * row["c_in"] * row["k"] + row["k"]
    return ITEMSIZE[dtype] * (acts + params)


def peaks(device_kind: str) -> dict:
    """The peak table's entry for this device; a device not in it is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(row: dict, peak: dict, batch: int = 1, dtype: str = "float32") -> float:
    """The least time the chip could take for one call of the layer."""
    return max(flops(row, batch) / peak["flops_per_s"], bytes_moved(row, batch, dtype) / peak["hbm_bytes_per_s"])


def total_flops(rows: list[dict], batch: int = 1) -> int:
    return int(np.sum([flops(r, batch) for r in rows], dtype=np.int64))
