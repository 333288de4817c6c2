"""Pallas TPU kernels for COAX's compute hot spots (paper §6 scans, §5 Alg. 1).

Each kernel file contains the ``pl.pallas_call`` + BlockSpec tiling; ``ops``
exposes padded jit'd wrappers; ``ref`` holds the pure-jnp oracles the tests
compare against.  All kernels are validated in interpret mode on CPU.  The
device-resident serving plane (``engine.device``, DESIGN.md §4) embeds
``fused_scan`` — probe + segment search + filter in ONE launch, hits
compacted in the same jitted program — as its per-wave program; it is the
kernel that compiles for a TPU v5e (``tests/test_tpu_compile.py``).
"""
from .fused_scan import fused_scan, fused_scan_call
from .ops import (bucket_histogram, fused_range_scan, range_scan_batch_query,
                  range_scan_query, split_by_margin)
from . import ref

__all__ = ["range_scan_query", "range_scan_batch_query", "fused_range_scan",
           "fused_scan", "fused_scan_call", "bucket_histogram",
           "split_by_margin", "ref"]
