"""Pallas TPU kernel fusing Algorithm 1's split loop (paper §5, Alg. 1 end).

For every record the split computes the displacement against the learned
soft-FD model and routes the record to the primary or outlier index:

    disp   = d - (m * x + b)
    inlier = (-eps_lb <= disp) & (disp <= eps_ub)

A scalar loop on the host; one fused multiply-compare pass on the TPU VPU.
The kernel also emits per-tile inlier counts, whose exclusive prefix sum
gives each tile its write offset for the stable partition performed by the
wrapper (``ops.margin_split``) — the TPU-idiomatic replacement for the
paper's row-at-a-time ``primary.insert/outlier.insert``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret

DEFAULT_TILE = 1024


def _margin_split_kernel(x_ref, d_ref, params_ref, disp_ref, mask_ref, count_ref):
    """disp/inlier/count for one (1, T) record tile.

    params_ref: (1, 8) f32 — [m, b, eps_lb, eps_ub, n_valid, ...]
    """
    t = x_ref.shape[1]
    pid = pl.program_id(0)

    m = params_ref[0, 0]
    b = params_ref[0, 1]
    eps_lb = params_ref[0, 2]
    eps_ub = params_ref[0, 3]
    n_valid = params_ref[0, 4]

    disp = d_ref[...] - (m * x_ref[...] + b)              # (1, T) fused FMA
    gid = pid * t + jax.lax.broadcasted_iota(jnp.float32, (1, t), 1)
    valid = gid < n_valid
    inlier = (disp >= -eps_lb) & (disp <= eps_ub) & valid

    disp_ref[...] = disp
    mask_ref[...] = inlier.astype(jnp.int32)
    count_ref[0, 0] = jnp.sum(inlier.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def margin_split(
    x: jax.Array,        # (N,) f32, N multiple of tile (ops pads)
    d: jax.Array,        # (N,) f32
    params: jax.Array,   # (8,) f32 — [m, b, eps_lb, eps_ub, n_valid, 0, 0, 0]
    *,
    tile: int = DEFAULT_TILE,
    interpret: Optional[bool] = None,
):
    """Returns ``(disp (N,), inlier_mask (N,) int32, tile_counts)``."""
    n = x.shape[0]
    if n % tile:
        raise ValueError(f"N={n} must be a multiple of tile={tile}")
    num_tiles = n // tile

    disp, mask, counts = pl.pallas_call(
        _margin_split_kernel,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, num_tiles), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(x[None, :], d[None, :], params[None, :])
    return disp[0], mask[0], counts[0]
