"""Pallas TPU kernels — the counterpart of the reference's hand-written CUDA
(``paddle/phi/kernels/gpu/``, ``paddle/fluid/operators/fused/``). Only ops
where XLA needs help live here; everything else is HLO.
"""
from . import cache_read, cache_write, flash_attention  # noqa: F401
