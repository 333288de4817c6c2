"""Pallas TPU kernel for Algorithm 1's grid bucketing (paper §5, Fig. 3).

The detection step histograms a 2-attribute sample onto a B x B grid.  A GPU
would scatter-add with atomics; on TPU (DESIGN.md §3) each grid program
builds the bucket assignment of its record tile as two one-hot matrices and
multiplies them on the MXU:

    hist_tile = onehot_x^T @ onehot_d        # (B, T) @ (T, B) -> (B, B)

The output BlockSpec maps every program to the SAME (B, B) block, so the
kernel accumulates in place across the sequential grid — the standard Pallas
revisiting-output reduction, no atomics required.

VMEM: two (T, B) one-hots + the (B, B) accumulator; with T=256, B=128 that is
2*128KiB + 64KiB at f32 — comfortably resident.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret

DEFAULT_TILE = 256


def _grid_histogram_kernel(x_ref, d_ref, params_ref, hist_ref):
    """Accumulate one record tile into the (B, B) bucket histogram.

    x_ref, d_ref : (1, T) f32 — the two attribute columns for this tile
    params_ref   : (1, 8) f32 — [x_lo, inv_wx, d_lo, inv_wd, n_valid, ...]
    hist_ref     : (B, B) f32 out — accumulated across all programs
    """
    t = x_ref.shape[1]
    b = hist_ref.shape[0]
    pid = pl.program_id(0)

    x_lo = params_ref[0, 0]
    inv_wx = params_ref[0, 1]
    d_lo = params_ref[0, 2]
    inv_wd = params_ref[0, 3]
    n_valid = params_ref[0, 4]

    # Padding rows (global id >= n_valid) contribute nothing.
    gid = pid * t + jax.lax.broadcasted_iota(jnp.float32, (1, t), 1)
    valid = gid < n_valid                                          # (1, T)

    ix = jnp.clip((x_ref[...] - x_lo) * inv_wx, 0, b - 1).astype(jnp.int32)
    jd = jnp.clip((d_ref[...] - d_lo) * inv_wd, 0, b - 1).astype(jnp.int32)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (t, b), 1)
    onehot_x = jnp.where((lanes == ix[0, :, None]) & valid[0, :, None], 1.0, 0.0)
    onehot_d = jnp.where((lanes == jd[0, :, None]) & valid[0, :, None], 1.0, 0.0)

    # MXU contraction over the record axis: (B, T) @ (T, B).
    tile_hist = jax.lax.dot_general(
        onehot_x, onehot_d,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pid == 0)
    def _init():
        hist_ref[...] = tile_hist

    @pl.when(pid > 0)
    def _acc():
        hist_ref[...] += tile_hist


@functools.partial(jax.jit, static_argnames=("buckets", "tile", "interpret"))
def grid_histogram(
    x: jax.Array,          # (N,) f32, N multiple of tile (ops pads)
    d: jax.Array,          # (N,) f32
    params: jax.Array,     # (8,) f32 — [x_lo, inv_wx, d_lo, inv_wd, n_valid, 0, 0, 0]
    *,
    buckets: int = 64,
    tile: int = DEFAULT_TILE,
    interpret: Optional[bool] = None,
):
    """Bucket-count the (x, d) sample onto a ``buckets x buckets`` grid."""
    n = x.shape[0]
    if n % tile:
        raise ValueError(f"N={n} must be a multiple of tile={tile}")
    num_tiles = n // tile

    hist = pl.pallas_call(
        _grid_histogram_kernel,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((buckets, buckets), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((buckets, buckets), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x[None, :], d[None, :], params[None, :])
    return hist
