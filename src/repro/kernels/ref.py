"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` mirrors its kernel's exact contract (same inputs incl. padding
and params vectors, same outputs) so the tests can ``assert_allclose`` across
shape/dtype sweeps — and ``fused_scan_ref`` is the CPU route of the device
serving plane's per-wave program (``engine.device``, DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["range_scan_ref", "range_scan_batch_ref", "fused_scan_ref",
           "fused_scan_words_ref", "grid_histogram_ref", "margin_split_ref"]


def range_scan_ref(rows_t, rect_lo, rect_hi, window, *, tile: int = 512):
    """Oracle for ``range_scan.range_scan``: (mask (N,), counts (num_tiles,))."""
    d, n = rows_t.shape
    inside = jnp.all(
        (rows_t >= rect_lo[:, None]) & (rows_t < rect_hi[:, None]), axis=0
    )
    gid = jnp.arange(n, dtype=jnp.int32)
    in_window = (gid >= window[0]) & (gid < window[1])
    mask = (inside & in_window).astype(jnp.int32)
    counts = mask.reshape(n // tile, tile).sum(axis=1)
    return mask, counts


def range_scan_batch_ref(rows_t, rect_lo_t, rect_hi_t, windows, *, tile: int = 512):
    """Oracle for ``range_scan_batch``: (mask (B, N), counts (B, num_tiles)).

    rect_lo_t/rect_hi_t are (D, B) bounds columns, windows is (B, 2) — the
    exact kernel contract including padding.
    """
    d, n = rows_t.shape
    lo = rect_lo_t.T[:, :, None]                               # (B, D, 1)
    hi = rect_hi_t.T[:, :, None]
    inside = jnp.all((rows_t[None] >= lo) & (rows_t[None] < hi), axis=1)  # (B, N)
    gid = jnp.arange(n, dtype=jnp.int32)[None, :]
    in_window = (gid >= windows[:, :1]) & (gid < windows[:, 1:])
    mask = (inside & in_window).astype(jnp.int32)
    counts = mask.reshape(mask.shape[0], n // tile, tile).sum(axis=2)
    return mask, counts


def fused_scan_ref(rows, flo, fhi, alive, coords=None, first=None,
                   last=None, sv=None, tband=None, gidx=None, *,
                   hit_cap: int = 1024):
    """Oracle for ``fused_scan.fused_scan`` — identical contract (the same
    lane-layout planes, ``fused_scan.to_lanes``), and the CPU route of the
    §4 device plane.

    Returns ``(counts (Bp, 1) i32, hits (Bp, hit_cap) i32, scanned (Bp, 1)
    i32)`` with ``hits[b, :min(counts[b], hit_cap)]`` the matching logical
    row positions ascending and ``-1`` after.

    Two things differ from the kernel, neither observable:

    * **Candidate-gather scan** (``gidx (Bp, R)`` i32 logical positions):
      each query's predicate evaluation runs over only its ``gidx`` rows —
      the device plane fills ``gidx`` with EXACTLY each query's
      probe-derived candidate-box row positions, ascending (each cell in
      the candidate coord box is a contiguous cell-major block), padded
      with the position of a dead ``+inf`` pad row.  Exact because every
      row a query can HIT is a member of its candidate box, each candidate
      appears exactly once, and pad slots fail the ``alive`` test.  The
      ``coords``/``first``/``last`` test is implied and skipped on this
      path.  This makes the CPU oracle scale with per-query candidate
      counts instead of table size; ``gidx=None`` scans the full array.
    * **Bisect compaction** over the running hit count instead of the
      kernel's bitmap words — same prefix.
    """
    hit, cand = _fused_hits(rows, flo, fhi, alive, coords, first, last,
                            sv, tband, gidx)

    running = jnp.cumsum(hit.astype(jnp.int32), axis=1)        # nondecreasing
    counts = running[:, -1:]
    scanned = cand.sum(axis=1, dtype=jnp.int32)[:, None]
    targets = jnp.arange(1, hit_cap + 1, dtype=jnp.int32)
    idx = jax.vmap(                        # j-th hit = first i with count j+1
        lambda r: jnp.searchsorted(r, targets, side="left"))(running)
    defined = targets[None, :] <= jnp.minimum(counts, hit_cap)
    if gidx is not None:                   # local slot -> global row position
        pos = jnp.take_along_axis(
            gidx, jnp.minimum(idx, gidx.shape[1] - 1), axis=1)
    else:
        pos = idx
    hits = jnp.where(defined, pos.astype(jnp.int32), -1)
    return counts, hits, scanned


def _fused_hits(rows, flo, fhi, alive, coords, first, last, sv, tband,
                gidx):
    """``fused_scan_ref``'s per-row test: ``(hit, cand)`` over every row
    ``(Bp, N)`` in logical order, or over each query's ``gidx`` rows."""
    from .fused_scan import from_lanes, lane_index

    d = rows.shape[0]
    bp = flo.shape[0]
    if gidx is not None:
        at = lane_index(gidx)
        flat = rows.reshape(d, -1)
        inside = jnp.ones(gidx.shape, bool)
        for j in range(d):
            inside &= (flat[j][at] >= flo[:, j:j + 1]) & (
                flat[j][at] < fhi[:, j:j + 1])
        cand = alive.reshape(-1)[at] > 0
        # coord-box membership is implied: gidx holds exactly the box rows
        if sv is not None:
            v = sv.reshape(-1)[at]
            cand = cand & (v >= tband[:, :1]) & (v < tband[:, 1:])
    else:
        rows_t = from_lanes(rows)
        n = rows_t.shape[1]
        inside = jnp.ones((bp, n), bool)
        for j in range(d):
            inside &= (rows_t[j][None, :] >= flo[:, j:j + 1]) & (
                rows_t[j][None, :] < fhi[:, j:j + 1])
        cand = jnp.broadcast_to(from_lanes(alive)[None, :] > 0, (bp, n))
        if coords is not None:
            cl = from_lanes(coords)
            for j in range(cl.shape[0]):
                cand = cand & (cl[j][None, :] >= first[:, j:j + 1]) & (
                    cl[j][None, :] <= last[:, j:j + 1])
        if sv is not None:
            v = from_lanes(sv)[None, :]
            cand = cand & (v >= tband[:, :1]) & (v < tband[:, 1:])
    hit = cand & inside
    return hit, cand


def fused_scan_words_ref(rows, flo, fhi, alive, coords=None, first=None,
                         last=None, sv=None, tband=None, gidx=None):
    """Oracle for ``fused_scan.fused_scan_words``: ``(Bp, N/32)`` int32 hit
    words, word ``w`` bit ``s`` being logical row ``32·w + s``.  With
    ``gidx`` the gathered rows' hits are put back at their positions (the
    pad slots all name one dead row, which never hits)."""
    hit, _ = _fused_hits(rows, flo, fhi, alive, coords, first, last, sv,
                         tband, gidx)
    bp = flo.shape[0]
    n = rows.shape[1] * rows.shape[2]
    if gidx is not None:
        hit = jnp.zeros((bp, n), bool).at[
            jnp.arange(bp)[:, None], gidx].max(hit)
    bits = hit.reshape(bp, n // 32, 32).astype(jnp.int32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.int32), axis=2)


def grid_histogram_ref(x, d, params, *, buckets: int = 64):
    """Oracle for ``grid_histogram.grid_histogram``: (B, B) f32 counts."""
    x_lo, inv_wx, d_lo, inv_wd, n_valid = params[0], params[1], params[2], params[3], params[4]
    n = x.shape[0]
    ix = jnp.clip((x - x_lo) * inv_wx, 0, buckets - 1).astype(jnp.int32)
    jd = jnp.clip((d - d_lo) * inv_wd, 0, buckets - 1).astype(jnp.int32)
    valid = jnp.arange(n, dtype=jnp.float32) < n_valid
    flat = ix * buckets + jd
    hist = jnp.zeros(buckets * buckets, dtype=jnp.float32).at[flat].add(
        valid.astype(jnp.float32)
    )
    return hist.reshape(buckets, buckets)


def margin_split_ref(x, d, params, *, tile: int = 1024):
    """Oracle for ``margin_split.margin_split``: (disp, mask, tile_counts)."""
    m, b, eps_lb, eps_ub, n_valid = params[0], params[1], params[2], params[3], params[4]
    n = x.shape[0]
    disp = d - (m * x + b)
    valid = jnp.arange(n, dtype=jnp.float32) < n_valid
    mask = ((disp >= -eps_lb) & (disp <= eps_ub) & valid).astype(jnp.int32)
    counts = mask.reshape(n // tile, tile).sum(axis=1)
    return disp, mask, counts
