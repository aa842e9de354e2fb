"""Heterogeneity model for the pipeline runtime.

On real chiplet hardware the FEP/SEP speed difference is physical.  On this
(homogeneous, CPU) box we keep the paper's semantics by attaching a derate
factor to each EP: measured per-layer times are scaled by the derate of the
EP a stage is mapped to.  The derates come from the same Platform
description the scheduler sees, so the online-tuning loop closes end to
end: measure -> scale -> Alg. 2 move -> re-measure.
"""

from __future__ import annotations

import dataclasses

from ..core.platform import EP, Platform, tpu_slice_ep


@dataclasses.dataclass(frozen=True)
class EPDerates:
    """Relative speed of each EP (1.0 = fastest)."""

    factors: tuple[float, ...]

    @classmethod
    def from_platform(cls, platform: Platform) -> "EPDerates":
        best = max(ep.flops for ep in platform.eps)
        return cls(tuple(best / ep.flops for ep in platform.eps))

    def scale(self, ep_idx: int, t: float) -> float:
        return t * self.factors[ep_idx]

    def compose(self, other: "EPDerates") -> "EPDerates":
        """Elementwise product of two derate vectors.

        The serving simulator uses this to merge independent derate
        sources — scripted platform faults and thermal throttling — into
        the one vector the drift detector observes.
        """
        if len(other.factors) != len(self.factors):
            raise ValueError(
                f"cannot compose derates over {len(self.factors)} and "
                f"{len(other.factors)} EPs"
            )
        return EPDerates(
            tuple(a * b for a, b in zip(self.factors, other.factors))
        )


def tpu_platform_from_mesh(n_stages: int, chips_per_stage: int = 8, slow_fraction: float = 0.5) -> Platform:
    """A Platform whose EPs are slices of a TPU mesh: one EP per pipeline
    stage, each a slice of ``chips_per_stage`` chips, the first
    ``slow_fraction`` of them modelled slow: the paper's chiplets, mapped
    onto slices."""
    n_slow = int(n_stages * slow_fraction)
    eps = [
        tpu_slice_ep(f"slice{i}", chips_per_stage, fast=(i >= n_slow))
        for i in range(n_stages)
    ]
    # fast first, as H_e expects descending performance
    eps.sort(key=lambda e: e.perf_class)
    return Platform(name=f"tpu-pipeline-{n_stages}", eps=tuple(eps))
