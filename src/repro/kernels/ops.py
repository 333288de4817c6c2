"""Public jit'd wrappers around the Pallas kernels.

These handle ragged sizes (padding to tile multiples), parameter packing,
and expose numpy-friendly entry points the COAX core and benchmarks call.
``use_pallas=False`` routes to the pure-jnp oracle (identical results) —
the default on CPU, where interpret-mode Pallas is a correctness tool, not
a fast path.  The device serving plane (``engine.device``, DESIGN.md §4)
bypasses these host-facing wrappers: it embeds ``fused_scan_call`` /
``ref.fused_scan_ref`` segments directly inside its own jitted wave program
with plan-resident pre-padded arrays; ``fused_range_scan`` below is the
standalone entry for tests and notebooks.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .fused_scan import fused_scan, pad_to_lanes, padded_rows
from .grid_histogram import grid_histogram
from .margin_split import margin_split
from .range_scan import range_scan
from .range_scan_batch import range_scan_batch

__all__ = [
    "range_scan_query",
    "range_scan_batch_query",
    "fused_range_scan",
    "bucket_histogram",
    "split_by_margin",
]


def _pad_to(arr: jnp.ndarray, multiple: int, value) -> jnp.ndarray:
    n = arr.shape[-1]
    rem = (-n) % multiple
    if rem == 0:
        return arr
    pad = [(0, 0)] * (arr.ndim - 1) + [(0, rem)]
    return jnp.pad(arr, pad, constant_values=value)


def range_scan_query(
    rows_t,                # (D, N) column-major records
    rect_lo,               # (D,)
    rect_hi,               # (D,)
    window=None,           # (2,) [lo, hi) scan window; None -> whole array
    *,
    tile: int = 512,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Count + mask for one translated query (paper §6 scan).

    Returns ``(count, mask (N,))`` where mask covers the ORIGINAL n records.
    """
    rows_t = jnp.asarray(rows_t, jnp.float32)
    d, n = rows_t.shape
    if window is None:
        window = jnp.array([0, n], jnp.int32)
    window = jnp.asarray(window, jnp.int32)
    padded = _pad_to(rows_t, tile, jnp.inf)  # +inf rows never match (< hi fails)
    if use_pallas:
        mask, counts = range_scan(
            padded, jnp.asarray(rect_lo, jnp.float32), jnp.asarray(rect_hi, jnp.float32),
            window, tile=tile, interpret=interpret,
        )
    else:
        mask, counts = ref.range_scan_ref(
            padded, jnp.asarray(rect_lo, jnp.float32), jnp.asarray(rect_hi, jnp.float32),
            window, tile=tile,
        )
    return counts.sum(), mask[:n]


def range_scan_batch_query(
    rows_t,                # (D, N) column-major records
    rect_lo,               # (B, D) per-query lower bounds
    rect_hi,               # (B, D) per-query upper bounds
    windows=None,          # (B, 2) per-query [lo, hi) scan windows; None -> whole
    *,
    tile: int = 512,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Counts + masks for a BATCH of translated queries in one device launch.

    Returns ``(counts (B,), mask (B, N))`` where each mask row covers the
    ORIGINAL n records.  The kernel wants bounds as (D, B) columns so one
    query's rect is a lane-resident block; this wrapper transposes.
    """
    rows_t = jnp.asarray(rows_t, jnp.float32)
    rect_lo = jnp.asarray(rect_lo, jnp.float32)
    rect_hi = jnp.asarray(rect_hi, jnp.float32)
    d, n = rows_t.shape
    b = rect_lo.shape[0]
    if windows is None:
        windows = jnp.broadcast_to(jnp.array([0, n], jnp.int32), (b, 2))
    windows = jnp.asarray(windows, jnp.int32)
    padded = _pad_to(rows_t, tile, jnp.inf)  # +inf rows never match (< hi fails)
    if use_pallas:
        mask, counts = range_scan_batch(
            padded, rect_lo.T, rect_hi.T, windows, tile=tile, interpret=interpret,
        )
    else:
        mask, counts = ref.range_scan_batch_ref(
            padded, rect_lo.T, rect_hi.T, windows, tile=tile,
        )
    return counts.sum(axis=1), mask[:, :n]


def fused_range_scan(
    rows_t,                # (D, N) column-major records
    rect_lo,               # (B, D) per-query ceil-rounded lower bounds
    rect_hi,               # (B, D) per-query ceil-rounded upper bounds
    alive=None,            # (N,) liveness; None -> all alive
    coords=None,           # (kk, N) per-dim cell coords (probe stage)
    first=None,            # (B, kk) per-query first cell coord
    last=None,             # (B, kk) per-query last cell coord
    sv=None,               # (N,) in-cell sorted attribute (sort stage)
    tband=None,            # (B, 2) per-query [t_lo, t_hi) sort targets
    *,
    hit_cap: int = 1024,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
):
    """Standalone megakernel entry: pads N to ``padded_rows(N)``, converts
    every per-row plane to the kernel's lane layout, and routes to the
    Pallas kernel or the jnp oracle.

    Returns ``(counts (B,), hits (B, hit_cap), scanned (B,))``; see
    ``fused_scan`` for the compacted-hits contract.  Positions ≥ the
    original N never appear (pads are dead: rows +inf, alive 0, coords -1).
    """
    rows_t = jnp.asarray(rows_t, jnp.float32)
    d, n = rows_t.shape
    n_pad = padded_rows(n)

    def plane(x, fill, dtype):
        return pad_to_lanes(jnp.asarray(x), n_pad, fill, dtype)

    if alive is None:
        alive = jnp.ones(n, jnp.int32)
    kwargs = {}
    if coords is not None:
        kwargs["coords"] = plane(coords, -1, jnp.int32)
        kwargs["first"] = jnp.asarray(first, jnp.int32)
        kwargs["last"] = jnp.asarray(last, jnp.int32)
    if sv is not None:
        kwargs["sv"] = plane(sv, jnp.inf, jnp.float32)
        kwargs["tband"] = jnp.asarray(tband, jnp.float32)
    fn = fused_scan if use_pallas else ref.fused_scan_ref
    extra = {"interpret": interpret} if use_pallas else {}
    counts, hits, scanned = fn(
        plane(rows_t, jnp.inf, jnp.float32),
        jnp.asarray(rect_lo, jnp.float32), jnp.asarray(rect_hi, jnp.float32),
        plane(alive, 0, jnp.int32), hit_cap=hit_cap, **kwargs, **extra)
    return counts[:, 0], hits, scanned[:, 0]


def bucket_histogram(
    x, d, *,
    buckets: int = 64,
    tile: int = 256,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Algorithm 1 bucket counts on the device; returns (B, B) float32."""
    x = jnp.asarray(x, jnp.float32)
    d = jnp.asarray(d, jnp.float32)
    n = x.shape[0]
    x_lo, x_hi = x.min(), x.max()
    d_lo, d_hi = d.min(), d.max()
    wx = jnp.maximum((x_hi - x_lo) / buckets, 1e-30)
    wd = jnp.maximum((d_hi - d_lo) / buckets, 1e-30)
    params = jnp.stack(
        [x_lo, 1.0 / wx, d_lo, 1.0 / wd,
         jnp.float32(n), jnp.float32(0), jnp.float32(0), jnp.float32(0)]
    )
    xp = _pad_to(x, tile, 0.0)
    dp = _pad_to(d, tile, 0.0)
    if use_pallas:
        return grid_histogram(xp, dp, params, buckets=buckets, tile=tile, interpret=interpret)
    return ref.grid_histogram_ref(xp, dp, params, buckets=buckets)


def split_by_margin(
    x, d, m, b, eps_lb, eps_ub, *,
    tile: int = 1024,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused Alg.-1 split: returns ``(disp (N,), inlier_mask (N,) bool)``."""
    x = jnp.asarray(x, jnp.float32)
    d = jnp.asarray(d, jnp.float32)
    n = x.shape[0]
    params = jnp.array([m, b, eps_lb, eps_ub, n, 0, 0, 0], jnp.float32)
    xp = _pad_to(x, tile, 0.0)
    dp = _pad_to(d, tile, 0.0)
    if use_pallas:
        disp, mask, _ = margin_split(xp, dp, params, tile=tile, interpret=interpret)
    else:
        disp, mask, _ = ref.margin_split_ref(xp, dp, params, tile=tile)
    return disp[:n], mask[:n].astype(bool)
