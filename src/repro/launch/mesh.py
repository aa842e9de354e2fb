"""The pipeline's stage mesh.

A function, so importing this module never touches jax device state.
``make_stage_mesh(n_stages, per_stage)`` lays the first ``n_stages *
per_stage`` devices out as a ("stage", "inner") mesh: one row of chips per
pipeline stage, the chiplet axis the paper schedules over.
``PipelineRunner`` (pipeline/runtime.py) runs one stage on each row and
ships activations along "stage" with ``ppermute``.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def make_stage_mesh(n_stages: int, per_stage: int = 1) -> Mesh:
    """Pipeline mesh for the Shisha runtime: ("stage", "inner")."""
    n = n_stages * per_stage
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(n_stages, per_stage), ("stage", "inner"))
