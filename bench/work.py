"""The bytes a query's work needs: the index's candidate rows.

A COAX query has to look at the rows of the grid cells its rect reaches,
inside the sorted column's band: per segment, the rows of every cell in
the probed box whose in-cell sort value lies in ``[lo, hi)`` of the nav
rect.  This is counted here from the frozen directory (inner edges,
offsets, sort values), never from the kernel's shapes, so a kernel that
reads only those rows stays within its roofline.  The numpy backend
reports the same count as ``rows_scanned``.

Needed bytes = candidate rows x D x 4 (each candidate's float32 row) plus
hits x 4 (each answer's int32 id), read or written once from HBM.
"""
from __future__ import annotations

import numpy as np


def grid_candidates(grid, nav: np.ndarray) -> np.ndarray:
    """Candidate rows of one ``GridFile`` per nav rect ``(Q, len(index_dims),
    2)``: cells by the f64 directory probe, rows by the sort band."""
    q = nav.shape[0]
    out = np.zeros(q, np.int64)
    if not grid.n_rows:
        return out
    c = grid.cells_per_dim
    pos = [grid.index_dims.index(d) for d in grid.grid_dims]
    first = np.stack([np.searchsorted(e, nav[:, p, 0], side="right")
                      for e, p in zip(grid.inner_edges, pos)], axis=1) \
        if pos else np.zeros((q, 0), np.int64)
    last = np.stack([np.searchsorted(e, nav[:, p, 1], side="left")
                     for e, p in zip(grid.inner_edges, pos)], axis=1) \
        if pos else np.zeros((q, 0), np.int64)
    sp = (grid.index_dims.index(grid.sort_dim)
          if grid.sort_dim is not None else None)
    off, sv = grid.offsets, grid.sort_vals
    for i in range(q):
        if np.any(last[i] < first[i]):
            continue
        cells = np.zeros(1, np.int64)
        for j in range(len(pos)):
            cells = (cells[:, None] * c
                     + np.arange(first[i, j], last[i, j] + 1)[None, :]).ravel()
        for cell in cells:
            a, b = off[cell], off[cell + 1]
            if sp is not None:
                blk = sv[a:b]
                a, b = (a + np.searchsorted(blk, nav[i, sp, 0], side="left"),
                        a + np.searchsorted(blk, nav[i, sp, 1], side="left"))
            out[i] += max(b - a, 0)
    return out


def candidate_rows(index, rects: np.ndarray) -> np.ndarray:
    """Candidate rows of a ``COAXIndex`` per full rect ``(Q, D, 2)``: the
    primary grid under the index's Eq. 2 translation, plus the outlier grid
    for rects that reach the outlier rows' bounding box."""
    rects = np.asarray(rects, np.float64)
    out = grid_candidates(index.primary, index.translate_batch(rects))
    o = index.outlier
    if o.n_rows:
        lo, hi = o.rows.min(axis=0), o.rows.max(axis=0)
        touch = np.all((rects[:, :, 0] <= hi) & (rects[:, :, 1] > lo), axis=1)
        out[touch] += grid_candidates(o, rects[touch])
    return out


def needed_bytes(candidates: np.ndarray, hits: np.ndarray, n_cols: int) -> int:
    return int(candidates.sum()) * n_cols * 4 + int(hits.sum()) * 4
