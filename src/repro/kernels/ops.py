"""Public entry points of the Pallas kernels.

Each kernel compiles to a Mosaic kernel for the TPU.  ``interpret``
defaults to False everywhere; a CPU caller (tests, micro-benchmarks) asks
for Pallas interpret mode explicitly with ``interpret=True``.
"""

from __future__ import annotations

from .flash_attention import flash_attention
from .gemm import gemm
from .im2col_conv import conv2d_im2col
from .ssd_scan import ssd_scan

__all__ = ["conv2d_im2col", "flash_attention", "gemm", "ssd_scan"]
