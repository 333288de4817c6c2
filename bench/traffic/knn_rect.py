"""The paper's kNN-rect query pool (COAX §8.1.2) at whole-table density.

A centre is a uniformly drawn row.  Its K nearest rows are taken over the
WHOLE table in std-normalised space, and the query is their per-column
``[min, nextafter(max))``, so a rect holds about K rows whatever the
table's size.  (``data/synth.py:knn_rect_queries`` takes the neighbours in
a fixed 200k-row subsample instead, so its boxes hold about
K * n / 200k rows and grow with the table.)

Everything runs on the device in one jitted call: a loop over blocks of
rows keeps each centre's K best, finding a block's with
``lax.approx_min_k`` (recall target 0.95, so a neighbour is now and then
passed over for the next one out) and merging them exactly.  Bounds are
float32 values, the table's own type, so a rect compares exactly in any
float32 or float64 code.

Parameters (the mix's ``pool``): ``k`` neighbours, ``size`` distinct
rects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK_ROWS = 16384      # rows per step: a (size, BLOCK_ROWS) f32 distance tile


@functools.partial(jax.jit, static_argnames=("k", "size", "block"))
def _pool(table, key, k: int, size: int, block: int = BLOCK_ROWS):
    d, n = table.shape
    block = min(block, n)
    scale = jnp.std(table, axis=1)
    inv = jnp.where(scale > 0, 1.0 / scale, 1.0)[:, None]
    centre_rows = jax.random.randint(key, (size,), 0, n)
    centres = table[:, centre_rows] * inv                     # (D, P)
    n_blocks = -(-n // block)

    def step(i, best):
        best_d, best_i = best
        start = jnp.minimum(i * block, n - block)     # last block overlaps
        rows = lax.dynamic_slice_in_dim(table, start, block, axis=1) * inv
        dist = jnp.zeros((size, block), jnp.float32)
        for j in range(d):
            dist += jnp.square(rows[j][None, :] - centres[j][:, None])
        pos = start + jnp.arange(block)
        dist = jnp.where(pos[None, :] >= i * block, dist, jnp.inf)
        bd, bi = lax.approx_min_k(dist, k)
        all_d = jnp.concatenate([best_d, bd], axis=1)
        all_i = jnp.concatenate([best_i, (bi + start).astype(jnp.int32)],
                                axis=1)
        neg, sel = lax.top_k(-all_d, k)
        return -neg, jnp.take_along_axis(all_i, sel, axis=1)

    init = (jnp.full((size, k), jnp.inf, jnp.float32),
            jnp.zeros((size, k), jnp.int32))
    _, nn = lax.fori_loop(0, n_blocks, step, init)
    pts = table[:, nn]                                        # (D, P, K)
    lo = pts.min(axis=2)
    hi = jnp.nextafter(pts.max(axis=2), jnp.float32(jnp.inf))
    return jnp.stack([lo.T, hi.T], axis=2)                    # (P, D, 2)


def make_pool(table, key, params: dict) -> np.ndarray:
    """``(size, D, 2)`` float64 rects (float32 values) from the device
    table ``(D, N)``."""
    rects = _pool(table, key, k=int(params["k"]), size=int(params["size"]))
    return np.asarray(rects).astype(np.float64)
