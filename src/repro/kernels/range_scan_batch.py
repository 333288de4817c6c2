"""Pallas TPU kernel for the batched COAX range scan (DESIGN.md §3; the
filter stage of the §4 device serving plane).

``range_scan.py`` evaluates ONE translated rectangle per launch; the batched
engine instead fuses B queries into a single ``pl.pallas_call`` so the record
block is streamed from HBM once per batch row rather than once per Python
round-trip, and the (D, TILE) tile in VMEM is reused across the whole rect
batch wavefront.

Layout: the grid is (num_tiles, B) — the LAST grid axis iterates fastest on
TPU, so b varies innermost.  Program (i, b) loads the shared record tile
``rows[:, i*TILE:(i+1)*TILE]`` plus query b's bounds column (rect lo/hi
stored (D, B) so each query's bounds are one (D, 1) lane-resident block) and
window row, and emits query b's per-record match mask and per-tile count.
The rows BlockSpec maps every b to the same tile, so the pipeline keeps the
tile resident across the whole rect batch — B predicate evaluations per HBM
fetch instead of B full passes over the record array.

VMEM per program: (D, TILE) f32 rows + two (D, 1) bound columns ≈ D*2 KiB at
TILE=512 — identical budget to the single-query kernel; batching lives
entirely in the grid, not the block shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret

DEFAULT_TILE = 512


def _range_scan_batch_kernel(rows_ref, lo_ref, hi_ref, win_ref, mask_ref, count_ref):
    """One (tile i, query b) program: rect predicate + window mask + count.

    rows_ref : (D, TILE) f32 — record block shared by all b at this i
    lo_ref   : (D, 1)   f32 — query b's lower bounds
    hi_ref   : (D, 1)   f32 — query b's upper bounds
    win_ref  : (1, 2)   i32 — query b's [scan_lo, scan_hi) window
    mask_ref : (1, TILE) i32 out — 1 where the record matches query b
    count_ref: (1, 1)   i32 out — matches for (b, tile i)
    """
    tile = rows_ref.shape[1]
    i = pl.program_id(0)

    rows = rows_ref[...]                                   # (D, TILE)
    lo = lo_ref[...]                                       # (D, 1)
    hi = hi_ref[...]
    inside = jnp.all((rows >= lo) & (rows < hi), axis=0)   # (TILE,)

    gid = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    in_window = (gid >= win_ref[0, 0]) & (gid < win_ref[0, 1])

    hit = in_window & inside[None, :]
    mask_ref[...] = hit.astype(jnp.int32)
    count_ref[0, 0] = jnp.sum(hit.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def range_scan_batch(
    rows_t: jax.Array,      # (D, N) f32, column-major records
    rect_lo_t: jax.Array,   # (D, B) f32 — one bounds column per query
    rect_hi_t: jax.Array,   # (D, B) f32
    windows: jax.Array,     # (B, 2) i32 — per-query [scan_lo, scan_hi)
    *,
    tile: int = DEFAULT_TILE,
    interpret: Optional[bool] = None,
):
    """Evaluate B translated queries over one record block in one launch.

    Returns ``(mask (B, N) int32, counts (B, num_tiles) int32)``.  N must be
    a multiple of ``tile`` (``ops.range_scan_batch_query`` pads).
    """
    d, n = rows_t.shape
    if n % tile:
        raise ValueError(f"N={n} must be a multiple of tile={tile}")
    b = rect_lo_t.shape[1]
    num_tiles = n // tile

    mask, counts = pl.pallas_call(
        _range_scan_batch_kernel,
        grid=(num_tiles, b),                               # b innermost: tile stays resident
        in_specs=[
            pl.BlockSpec((d, tile), lambda i, b: (0, i)),  # rows: shared tile
            pl.BlockSpec((d, 1), lambda i, b: (0, b)),     # lo: query column
            pl.BlockSpec((d, 1), lambda i, b: (0, b)),     # hi: query column
            pl.BlockSpec((1, 2), lambda i, b: (b, 0)),     # window: query row
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i, b: (b, i)),
            pl.BlockSpec((1, 1), lambda i, b: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), jnp.int32),
            jax.ShapeDtypeStruct((b, num_tiles), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(rows_t, rect_lo_t, rect_hi_t, windows)
    return mask, counts
