"""Pallas TPU kernel for the COAX scan-between-bounds hot loop (paper §6).

The paper's C implementation binary-searches the in-cell sorted attribute and
then linearly scans rows, testing the (translated) query rectangle per row.
On TPU the scan is re-blocked (DESIGN.md §3): rows are stored column-major
(D, N) so the record axis lies along the 128-wide vector lanes; each grid
program streams one (D, TILE) block from HBM into VMEM, evaluates the whole
rectangle predicate for TILE records with predicated vector compares, masks
records outside the [lo, hi) scan window, and emits

  * a per-record match mask   (the gather/driver consumes it), and
  * a per-tile match count    (for two-pass count/allocate query execution).

Divergence-free: out-of-window tiles still execute but contribute zeros — the
wrapper in ``ops.py`` restricts the grid to the touched tile range instead.

Block shapes: TILE defaults to 512 lanes (4 VREGs deep at f32) and the full
attribute dimension D sits along sublanes; (D, 512) f32 = D*2KiB of VMEM per
operand, far under the ~16 MiB/core budget even with D=8 and double buffering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret

DEFAULT_TILE = 512


def _range_scan_kernel(rows_ref, lo_ref, hi_ref, win_ref, mask_ref, count_ref):
    """One (D, TILE) block: rectangle predicate + window mask + tile count.

    rows_ref : (D, TILE) f32 — column-major record block
    lo_ref   : (D, 1)   f32 — rectangle lower bounds (broadcast over lanes)
    hi_ref   : (D, 1)   f32 — rectangle upper bounds
    win_ref  : (1, 2)   i32 — [scan_lo, scan_hi) window in global row ids
    mask_ref : (1, TILE) i32 out — 1 where the record matches
    count_ref: (1, 1)   i32 out — number of matches in this tile
    """
    tile = rows_ref.shape[1]
    pid = pl.program_id(0)

    rows = rows_ref[...]                                   # (D, TILE)
    lo = lo_ref[...]                                       # (D, 1)
    hi = hi_ref[...]
    inside = jnp.all((rows >= lo) & (rows < hi), axis=0)   # (TILE,)

    # Global record ids of this tile -> window predicate.
    gid = pid * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    win_lo = win_ref[0, 0]
    win_hi = win_ref[0, 1]
    in_window = (gid >= win_lo) & (gid < win_hi)           # (1, TILE)

    hit = in_window & inside[None, :]
    mask_ref[...] = hit.astype(jnp.int32)
    count_ref[0, 0] = jnp.sum(hit.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def range_scan(
    rows_t: jax.Array,      # (D, N) f32, column-major records
    rect_lo: jax.Array,     # (D,)  f32
    rect_hi: jax.Array,     # (D,)  f32
    window: jax.Array,      # (2,)  i32 — [scan_lo, scan_hi)
    *,
    tile: int = DEFAULT_TILE,
    interpret: Optional[bool] = None,
):
    """Evaluate one translated query over a record block.

    Returns ``(mask (N,) int32, counts (num_tiles,) int32)``.  N must be a
    multiple of ``tile`` (``ops.range_scan_query`` pads).
    """
    d, n = rows_t.shape
    if n % tile:
        raise ValueError(f"N={n} must be a multiple of tile={tile}")
    num_tiles = n // tile

    mask, counts = pl.pallas_call(
        _range_scan_kernel,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((d, tile), lambda i: (0, i)),   # rows: stream tiles
            pl.BlockSpec((d, 1), lambda i: (0, 0)),      # rect lo: resident
            pl.BlockSpec((d, 1), lambda i: (0, 0)),      # rect hi: resident
            pl.BlockSpec((1, 2), lambda i: (0, 0)),      # window: resident
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, num_tiles), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(rows_t, rect_lo[:, None], rect_hi[:, None], window[None, :])
    return mask[0], counts[0]
