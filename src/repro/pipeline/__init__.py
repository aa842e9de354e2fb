"""Shisha-scheduled pipeline runtime (shard_map + ppermute micro-batching)."""

from .hetero import EPDerates, tpu_platform_from_mesh
from .runtime import MeasuringEvaluator, PipelineRunner

__all__ = [
    "EPDerates",
    "MeasuringEvaluator",
    "PipelineRunner",
    "tpu_platform_from_mesh",
]
