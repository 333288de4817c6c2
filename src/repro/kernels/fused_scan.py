"""Fused probe+search+filter Pallas megakernel (DESIGN.md §4).

One ``pl.pallas_call`` evaluates, for every (query b, row tile) it visits,
the WHOLE per-row serving predicate:

  ``hit[p] = alive[p] ∧ candidate[p] ∧ full-predicate[p]``

* ``candidate`` replaces both the directory probe and the in-cell bisect:
  the host passes the per-query per-grid-dim cell range ``[first, last]``
  (ONE conservative-f32 directory pass) and the kernel tests each row's
  precomputed cell coordinates against it, plus the in-cell sorted
  attribute against ``[t_lo, t_hi)``.  Because rows are stored cell-major
  and cell-sorted, this membership test selects exactly the rows of the
  numpy path's refined candidate blocks.
* ``full-predicate`` is the ceil-rounded f32 rect compare (`f32_ceil`
  pairing makes it bit-equal to the f64 host compare).
* ``alive`` masks tombstoned snapshot rows and padding, so the §5
  delta/tombstone scan runs in the same launch (``probe=False`` segments
  scan an append-log block with candidacy ≡ alive).

Which tiles a query visits.  A tile is one grid step's block of
``tile_rows(N)`` rows (32768, or the whole image when smaller).  Two
kernels share the body:

* the **listed** scan runs a ``(W,)`` grid over a host-built work list of
  ``(tile, query)`` items, scalar-prefetched into SMEM as one flat
  ``(2W,)`` int32 vector (tiles, then queries).  The block index maps read
  ``tile[w]`` and the SMEM bounds are read at ``query[w]``.  Items go query
  by query, tiles ascending and unique per query; items past the last real
  one carry query ``-1`` and repeat its tile, so Pallas issues no DMA for
  them and the body only writes zero words.  Each item writes its own
  ``(G, 128)`` words block and a ``(1, 128)`` candidate count.
* the **full** scan runs the ``(Bp, N / tile_rows)`` grid over every tile
  for every query.

``fused_scan_call`` given a work list runs the listed scan, or the full
scan when the host marks the list as overflowing (``work[0] < 0``) — both
in one executable, under ``lax.cond``.  Exactness: every listed tile still
runs the full per-row test ``alive ∧ coords∈[first,last] ∧ sv∈band ∧
rect``; only tiles that hold none of a query's candidate rows are left
out, and a query's tiles go ascending, so its hit positions stay
ascending.  Counts, hit prefixes and ``scanned`` equal the full scan's.

Layout (what Mosaic lowers).  Per-row planes are stored in the LANE
layout ``(..., N/128, 128)``: each 4096-row group is a ``(32, 128)`` slab
whose element ``(s, l)`` holds logical row ``32·l + s`` (``to_lanes``).
Per-query scalars (bounds, cell ranges, sort band) live whole in SMEM as
flat vectors.  Each grid step streams a ``(·, 256, 128)`` block (or the
whole image when smaller) and packs its hit mask into ``(G, 128)`` int32
WORDS (``G`` = 8 for a full tile) by summing ``hit << s`` over each slab's
32 sublanes — so word ``w`` bit ``s`` is logical row ``32·w + s`` of the
tile, in ascending row order.

Compaction happens in XLA, in the same jitted program: popcounts →
per-4096-row group counts → a cumsum and a bisection locate the group,
word and bit of each of the first ``hit_cap`` hits (``compact_hits`` over
a full scan's ``(Bp, N/4096, 128)`` bitmap, ``compact_listed`` over the
listed scan's ``(W, G, 128)`` item words, whose positions map back through
``tile[item]``).  Outputs are the exact per-query hit count (even past
``hit_cap``), the first ``min(count, hit_cap)`` hit positions ascending,
and the candidate-rows-scanned counter.

``ref.fused_scan_ref`` is the pure-jnp oracle with the identical contract
(same lane-layout inputs); it is the CPU route of the device plane's
jitted wave program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import resolve_interpret

LANES = 128
WORD_BITS = 32
GROUP_ROWS = WORD_BITS * LANES          # rows behind one row of 128 hit words
STEP_GROUPS = 8                         # word rows per grid step: (8, 128)
STEP_ROWS = STEP_GROUPS * GROUP_ROWS    # 32768 rows streamed per grid step
DEFAULT_HIT_CAP = 1024
LISTED_CHUNK = 32                       # hit slots a query locates per pass

__all__ = ["fused_scan", "fused_scan_call", "fused_scan_words",
           "compact_hits", "compact_listed", "compact_range",
           "to_lanes", "pad_to_lanes", "from_lanes", "lane_index",
           "padded_rows", "tile_rows", "GROUP_ROWS", "STEP_ROWS",
           "DEFAULT_HIT_CAP"]


def padded_rows(n: int) -> int:
    """Smallest row count >= ``n`` the kernel grid accepts: a multiple of
    ``GROUP_ROWS`` up to one step, of ``STEP_ROWS`` beyond it."""
    unit = GROUP_ROWS if n <= STEP_ROWS else STEP_ROWS
    return max(unit, -(-n // unit) * unit)


def tile_rows(n: int) -> int:
    """Rows one grid step reads from an image of ``n`` (padded) rows."""
    return min(n, STEP_ROWS)


def to_lanes(x):
    """Logical ``(..., N)`` -> lane layout ``(..., N/128, 128)`` where each
    4096-row group is a ``(32, 128)`` slab holding row ``32·l + s`` at
    ``(s, l)``.  Works on numpy and jax arrays; N % GROUP_ROWS == 0."""
    lead, n = x.shape[:-1], x.shape[-1]
    g = x.reshape(*lead, n // GROUP_ROWS, LANES, WORD_BITS)
    return g.swapaxes(-1, -2).reshape(*lead, n // LANES, LANES)


def pad_to_lanes(x, n_pad: int, fill, dtype):
    """Logical ``(..., n)`` planes padded with ``fill`` to ``n_pad`` rows,
    in the lane layout (``to_lanes``).  A numpy input gives a numpy result
    built one plane at a time, so the host holds one padded plane beyond
    the result; anything else goes through ``jnp``."""
    if isinstance(x, np.ndarray):
        lead, n = x.shape[:-1], x.shape[-1]
        src = x.reshape(-1, n)
        out = np.empty((src.shape[0], n_pad // LANES, LANES), dtype)
        buf = np.full(n_pad, fill, dtype)
        for j in range(src.shape[0]):
            buf[:n] = src[j]
            out[j] = to_lanes(buf)
        return out.reshape(*lead, n_pad // LANES, LANES)
    x = jnp.asarray(x, dtype)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n_pad - x.shape[-1])]
    return to_lanes(jnp.pad(x, pad, constant_values=fill))


def from_lanes(x):
    """Inverse of ``to_lanes``: ``(..., N/128, 128)`` -> ``(..., N)``."""
    lead, s = x.shape[:-2], x.shape[-2]
    g = x.reshape(*lead, s // WORD_BITS, WORD_BITS, LANES)
    return g.swapaxes(-1, -2).reshape(*lead, s * LANES)


def lane_index(p):
    """Flat lane-layout index of logical row position ``p``."""
    return ((p // GROUP_ROWS) * GROUP_ROWS + (p % WORD_BITS) * LANES
            + (p % GROUP_ROWS) // WORD_BITS)


def _make_kernel(d: int, kk: int, has_sort: bool, groups: int, nw: int):
    """Kernel body specialised to which predicate stages this segment has;
    ``nw > 0`` makes it the listed scan over a ``(nw,)`` work list.

    Ref order (present refs only):
      SMEM: work (2·nw,) i32 (listed only: tiles, then queries) |
            flo (Bp·D,) f32, fhi (Bp·D,) f32 | first, last (Bp·kk,) i32 |
            tband (Bp·2,) f32
      VMEM: rows (D, S, 128) f32 | coords (kk, S, 128) i32 |
            sv (S, 128) f32 | alive (S, 128) i32
      -> words (G, 128) i32, and scanned: (G, 128) i32 resident per query
         (full) or (1, 128) i32 per item (listed)
    """

    def kernel(*refs):
        it = iter(refs)
        work_ref = next(it) if nw else None
        flo_ref, fhi_ref = next(it), next(it)
        first_ref = next(it) if kk else None
        last_ref = next(it) if kk else None
        tband_ref = next(it) if has_sort else None
        rows_ref = next(it)
        coords_ref = next(it) if kk else None
        sv_ref = next(it) if has_sort else None
        alive_ref = next(it)
        words_ref, scanned_ref = next(it), next(it)

        def scan_tile(b):
            """Hit words and per-lane candidate counts of query ``b`` over
            this step's tile."""
            cand = alive_ref[...] > 0                          # (S, 128)
            for j in range(kk):
                c = coords_ref[j]
                cand &= ((c >= first_ref[b * kk + j])
                         & (c <= last_ref[b * kk + j]))
            if has_sort:
                sv = sv_ref[...]
                cand &= ((sv >= tband_ref[2 * b])
                         & (sv < tband_ref[2 * b + 1]))
            hit = cand
            for j in range(d):
                r = rows_ref[j]
                hit &= (r >= flo_ref[b * d + j]) & (r < fhi_ref[b * d + j])
            shape = (groups, WORD_BITS, LANES)
            bit = lax.broadcasted_iota(jnp.int32, shape, 1)
            words = jnp.sum(lax.shift_left(
                hit.astype(jnp.int32).reshape(shape), bit), axis=1)
            return words, jnp.sum(cand.astype(jnp.int32).reshape(shape),
                                  axis=1)

        if not nw:
            words, n_cand = scan_tile(pl.program_id(0))
            words_ref[...] = words

            @pl.when(pl.program_id(1) == 0)
            def _init():                 # fresh resident accumulator per query
                scanned_ref[...] = jnp.zeros_like(scanned_ref)

            scanned_ref[...] += n_cand
            return

        b = work_ref[nw + pl.program_id(0)]

        @pl.when(b >= 0)
        def _item():
            words, n_cand = scan_tile(b)
            words_ref[...] = words
            scanned_ref[...] = jnp.sum(n_cand, axis=0, keepdims=True)

        @pl.when(b < 0)
        def _pad():                      # past the last item: zero words
            words_ref[...] = jnp.zeros_like(words_ref)
            scanned_ref[...] = jnp.zeros_like(scanned_ref)

    return kernel


def _in_group(w, rank):
    """Word and bit of the ``rank``-th (1-based) set bit in ``w``, each
    ``(..., 128)`` int32 words of one 4096-row group in row order: the word
    whose running popcount reaches ``rank``, then the bit whose running
    count reaches what is left of it."""
    pcw = lax.population_count(w)
    wcum = jnp.cumsum(pcw, axis=-1)
    wi = jnp.minimum(jnp.sum(wcum < rank[..., None], axis=-1, keepdims=True),
                     LANES - 1)
    rank = rank - jnp.take_along_axis(wcum - pcw, wi, axis=-1)[..., 0]
    word = jnp.take_along_axis(w, wi, axis=-1)
    bits = (word.astype(jnp.uint32)
            >> jnp.arange(WORD_BITS, dtype=jnp.uint32)) & 1
    bi = jnp.sum(jnp.cumsum(bits.astype(jnp.int32), axis=-1)
                 < rank[..., None], axis=-1)
    return wi[..., 0], bi


@jax.named_scope("compact_hits")    # in the trace, apart from the kernel
def compact_hits(words, hit_cap: int):
    """Hit bitmaps ``(Bp, N/4096, 128)`` i32 -> ``(counts (Bp, 1) i32,
    hits (Bp, hit_cap) i32)``: each query's true hit count and its first
    ``min(count, hit_cap)`` hit positions ascending, ``-1`` after.

    Word ``w`` bit ``s`` of a row is logical position ``32·w + s``.  The
    j-th hit (1-based ``t``) sits in the first group whose inclusive count
    reaches ``t``; inside it, the word and bit ``_in_group`` finds."""
    pc = lax.population_count(words)                        # (Bp, G, 128)
    grp = jnp.sum(pc, axis=2)                               # (Bp, G)
    cum = jnp.cumsum(grp, axis=1)
    counts = cum[:, -1:]
    tgt = jnp.arange(1, hit_cap + 1, dtype=jnp.int32)
    g = jax.vmap(lambda c: jnp.searchsorted(c, tgt, side="left"))(cum)
    g = jnp.minimum(g, grp.shape[1] - 1).astype(jnp.int32)  # (Bp, cap)
    rank = tgt[None, :] - jnp.take_along_axis(cum - grp, g, axis=1)
    w = jnp.take_along_axis(words, g[:, :, None], axis=1)   # (Bp, cap, 128)
    wi, bi = _in_group(w, rank)
    pos = (g * LANES + wi) * WORD_BITS + bi
    defined = tgt[None, :] <= jnp.minimum(counts, hit_cap)
    return counts, jnp.where(defined, pos, -1).astype(jnp.int32)


@jax.named_scope("compact_listed")
def compact_listed(words, scanned, work, bp: int, hit_cap: int,
                   rows_per_tile: int):
    """Item words ``(W, G, 128)`` i32 of the listed scan -> ``(counts (bp,
    1), hits (bp, hit_cap), scanned (bp, 1))``, the full scan's contract.

    ``work`` is the flat ``(2W,)`` list (tiles, then queries; ``-1`` past
    the last item, whose words are zero).  Items go query by query, so a
    query's hits are one contiguous stretch of the running count over all
    items' groups, starting after the hits of every lower query: its
    ``t``-th hit has global rank ``base[b] + t``.  Hits are located
    ``LISTED_CHUNK`` slots per query at a time, for as many passes as the
    wave's largest ``min(count, hit_cap)`` needs, and each position maps
    back through ``tile[item]``.  The running count is at most ``W`` tiles
    of rows, so it fits int32 whatever the wave."""
    nw, g_per, _ = words.shape
    tiles, qry = work[:nw], jnp.maximum(work[nw:], 0)
    grp = jnp.sum(lax.population_count(words), axis=2).reshape(-1)
    cum = jnp.cumsum(grp)
    below = cum - grp                                       # exclusive
    counts = jax.ops.segment_sum(grp.reshape(nw, g_per).sum(axis=1), qry,
                                 num_segments=bp)
    base = jnp.cumsum(counts) - counts
    take = jnp.minimum(counts, hit_cap)
    chunk = min(LISTED_CHUNK, hit_cap)
    passes = -(-hit_cap // chunk)
    flat = words.reshape(nw * g_per, LANES)

    def locate(c, hits):
        t = c * chunk + jnp.arange(1, chunk + 1, dtype=jnp.int32)
        gt = base[:, None] + t[None, :]                     # (bp, chunk)
        g = jnp.minimum(jnp.searchsorted(cum, gt, side="left"),
                        nw * g_per - 1).astype(jnp.int32)
        wi, bi = _in_group(flat[g], gt - below[g])
        pos = (tiles[g // g_per] * rows_per_tile
               + ((g % g_per) * LANES + wi) * WORD_BITS + bi)
        pos = jnp.where(t[None, :] <= take[:, None], pos, -1)
        return lax.dynamic_update_slice(hits, pos.astype(jnp.int32),
                                        (0, c * chunk))

    n_pass = -(-jnp.max(take) // chunk)
    hits = lax.fori_loop(0, n_pass, locate,
                         jnp.full((bp, passes * chunk), -1, jnp.int32))
    scanned = jax.ops.segment_sum(jnp.sum(scanned, axis=(1, 2)), qry,
                                  num_segments=bp)
    return counts[:, None], hits[:, :hit_cap], scanned[:, None]


def _stage_operands(rows, flo, fhi, alive, coords, first, last, sv, tband):
    """Flat operand list in the kernel's ref order (SMEM scalars, then the
    per-row planes) and the stage flags."""
    kk = 0 if coords is None else coords.shape[0]
    has_sort = sv is not None
    smem = [flo.reshape(-1), fhi.reshape(-1)]
    if kk:
        smem += [first.reshape(-1), last.reshape(-1)]
    if has_sort:
        smem.append(tband.reshape(-1))
    planes = [rows] + ([coords] if kk else []) + ([sv] if has_sort else [])
    return smem, planes + [alive], kk, has_sort


def _plane_specs(planes, step, index):
    """Block specs of the per-row planes: ``step`` lane rows of the tile
    ``index(*grid_args)`` picks."""
    specs = []
    for p in planes:
        if p.ndim == 3:
            specs.append(pl.BlockSpec(
                (p.shape[0], step, LANES),
                lambda *a: (0, index(*a), 0)))
        else:
            specs.append(pl.BlockSpec((step, LANES),
                                      lambda *a: (index(*a), 0)))
    return specs


def _full_words(smem, planes, bp, kk, has_sort, interpret):
    """Every tile for every query: the ``(Bp, tiles)`` grid.  Returns the
    hit words ``(Bp, N/4096, 128)`` and the resident candidate counts."""
    d, s, _ = planes[0].shape
    step = tile_rows(s * LANES) // LANES
    groups = step // WORD_BITS
    spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _make_kernel(d, kk, has_sort, groups, 0),
        grid=(bp, s // step),          # steps innermost: resident scanned
        in_specs=[spec] * len(smem) + _plane_specs(
            planes, step, lambda b, i: i),
        out_specs=[
            pl.BlockSpec((None, groups, LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, groups, LANES), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, s // WORD_BITS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((bp, groups, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="coax_fused_scan",
    )(*smem, *planes)


def _full_scan(smem, planes, bp, kk, has_sort, hit_cap, interpret):
    words, scanned = _full_words(smem, planes, bp, kk, has_sort, interpret)
    counts, hits = compact_hits(words, hit_cap)
    return counts, hits, jnp.sum(scanned, axis=(1, 2))[:, None]


def _listed_words(work, smem, planes, kk, has_sort, interpret):
    """The work list's items only: the ``(W,)`` grid.  Returns the item
    words ``(W, G, 128)`` and each item's candidate count."""
    d, s, _ = planes[0].shape
    nw = work.shape[0] // 2
    step = tile_rows(s * LANES) // LANES
    groups = step // WORD_BITS
    spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _make_kernel(d, kk, has_sort, groups, nw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nw,),
            in_specs=[spec] * len(smem) + _plane_specs(
                planes, step, lambda w, wk: wk[w]),
            out_specs=[
                pl.BlockSpec((None, groups, LANES), lambda w, wk: (w, 0, 0)),
                pl.BlockSpec((None, 1, LANES), lambda w, wk: (w, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nw, groups, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nw, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="coax_fused_scan",
    )(work, *smem, *planes)


def _listed_scan(work, smem, planes, bp, kk, has_sort, hit_cap, interpret):
    words, scanned = _listed_words(work, smem, planes, kk, has_sort,
                                   interpret)
    step = tile_rows(planes[0].shape[1] * LANES)
    return compact_listed(words, scanned, work, bp, hit_cap, step)


def fused_scan_call(
    rows,              # (D, S, 128) f32 lane layout, pads +inf
    flo,               # (Bp, D) f32 ceil-rounded lower bounds
    fhi,               # (Bp, D) f32 ceil-rounded upper bounds
    alive,             # (S, 128) i32, 0 for tombstoned/padding rows
    coords=None,       # (kk, S, 128) i32 per-dim cell coords (pads -1); probe
    first=None,        # (Bp, kk) i32 per-query first cell coord;     segments
    last=None,         # (Bp, kk) i32 per-query last cell coord;      only
    sv=None,           # (S, 128) f32 in-cell sorted attribute (pads +inf)
    tband=None,        # (Bp, 2) f32 ceil-rounded [t_lo, t_hi) sort targets
    work=None,         # (2W,) i32 work list: tiles, then queries (-1 pads)
    *,
    hit_cap: int = DEFAULT_HIT_CAP,
    interpret: Optional[bool] = None,
):
    """Launch the megakernel over one segment and compact its hits; see the
    module docstring.  ``S·128`` must equal ``padded_rows(S·128)``.

    Returns ``(counts (Bp, 1) i32, hits (Bp, hit_cap) i32, scanned (Bp, 1)
    i32)``.  Probe/sort stages are enabled by passing their operands
    (all-or-none per stage).  Without ``work`` every query scans every
    tile; with it, the listed scan runs, or the full scan when ``work[0]``
    is negative (the host's mark of a list that did not fit).  Not jitted —
    the device plane embeds this in its own jitted wave program;
    ``fused_scan`` is the standalone entry.
    """
    n = rows.shape[1] * LANES
    if n != padded_rows(n):
        raise ValueError(f"N={n} rows is not a kernel grid size "
                         f"(padded_rows gives {padded_rows(n)})")
    bp = flo.shape[0]
    smem, planes, kk, has_sort = _stage_operands(
        rows, flo, fhi, alive, coords, first, last, sv, tband)
    interpret = resolve_interpret(interpret)
    full = functools.partial(_full_scan, smem, planes, bp, kk, has_sort,
                             hit_cap, interpret)
    if work is None:
        return full()
    listed = functools.partial(_listed_scan, work, smem, planes, bp, kk,
                               has_sort, hit_cap, interpret)
    return lax.cond(work[0] < 0, full, listed)


def fused_scan_words(rows, flo, fhi, alive, coords=None, first=None,
                     last=None, sv=None, tband=None, work=None, *,
                     interpret: Optional[bool] = None):
    """The megakernel's hit bitmap, uncompacted: ``(Bp, N/32)`` int32 words,
    word ``w`` bit ``s`` being logical row ``32·w + s``, for every query.

    The same scan as ``fused_scan_call`` (listed, or full when ``work[0]``
    is negative or absent), for the large-answer pass, which compacts the
    words with ``compact_range``.  A listed item's words land at its
    ``(query, tile)``; tiles a query's list leaves out hold none of its
    hits, so their words are zero."""
    n = rows.shape[1] * LANES
    bp = flo.shape[0]
    smem, planes, kk, has_sort = _stage_operands(
        rows, flo, fhi, alive, coords, first, last, sv, tband)
    interpret = resolve_interpret(interpret)

    def full():
        words, _ = _full_words(smem, planes, bp, kk, has_sort, interpret)
        return words.reshape(bp, n // WORD_BITS)

    if work is None:
        return full()

    def listed():
        words, _ = _listed_words(work, smem, planes, kk, has_sort,
                                 interpret)
        nw, groups, _ = words.shape
        tiles = n // (groups * GROUP_ROWS)
        qry = work[nw:]
        dense = jnp.zeros((bp, tiles, groups * LANES), jnp.int32)
        dense = dense.at[jnp.where(qry >= 0, qry, bp), work[:nw]].set(
            words.reshape(nw, groups * LANES), mode="drop")
        return dense.reshape(bp, n // WORD_BITS)

    return lax.cond(work[0] < 0, full, listed)


def _select_bit(word, rank):
    """Bit index of the ``rank``-th (0-based) set bit of each uint32
    ``word``: a binary search over the popcounts of its low halves."""
    at = jnp.zeros(word.shape, jnp.uint32)
    rank = rank.astype(jnp.uint32)
    for width in (16, 8, 4, 2, 1):
        low = lax.population_count(
            (word >> at) & jnp.uint32((1 << width) - 1))
        up = rank >= low
        at = at + jnp.where(up, jnp.uint32(width), jnp.uint32(0))
        rank = rank - jnp.where(up, low, jnp.uint32(0))
    return at.astype(jnp.int32)


@jax.named_scope("compact_range")
def compact_range(words, sel, off, chunk: int):
    """Positions of hits ``off .. off + chunk - 1`` (0-based, in row order)
    of the selected queries: ``words (Bp, M)`` i32 from
    ``fused_scan_words``, ``sel (kb,)`` i32 query rows (``>= Bp``: none),
    ``off`` a traced scalar -> ``(kb, chunk)`` i32, ``-1`` past a query's
    count.

    Hit ``r`` lies in the last word whose first hit's rank ``s_w`` (the
    running popcount before it) is at most ``r``.  So a value ``v_w`` of
    that word reaches slot ``r`` as a running sum over slots of the
    differences ``v_w - v_{w-1}``, each added at slot ``s_w``: the sum
    telescopes to the last such word's value.  Three such sums give each
    slot its word's index, bits and ``s_w``, and the bit is a five-step
    search over popcounts.  The ``s_w`` ascend, so every add is one
    sorted scatter and every read a cumsum, with no per-slot gather or
    search, which a TPU runs slowly.  Word bits wrap in int32, exactly;
    running counts are at most ``N``, which fits int32."""
    w = jnp.take(words, sel, axis=0, mode="fill", fill_value=0)
    kb, m = w.shape
    pc = lax.population_count(w)
    cw = jnp.cumsum(pc, axis=1)                          # inclusive
    first = cw - pc                                      # s_w
    # slots before the chunk fold into its first; past it, dropped
    at = jnp.clip(first - off, 0, chunk)
    row = jnp.broadcast_to(jnp.arange(kb)[:, None], (kb, m))

    def running(step):
        acc = jnp.zeros((kb, chunk), jnp.int32).at[row, at].add(
            step, mode="drop", indices_are_sorted=True)
        return jnp.cumsum(acc, axis=1)

    def diff(v):
        return v - jnp.pad(v, ((0, 0), (1, 0)))[:, :-1]

    wi = running(jnp.ones_like(w)) - 1
    word = lax.bitcast_convert_type(running(diff(w)), jnp.uint32)
    slot = off + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    pos = wi * WORD_BITS + _select_bit(word, slot - running(diff(first)))
    return jnp.where(slot < cw[:, -1:], pos, -1)


@functools.partial(jax.jit, static_argnames=("hit_cap", "interpret"))
def fused_scan(rows, flo, fhi, alive, coords=None, first=None, last=None,
               sv=None, tband=None, work=None, *,
               hit_cap: int = DEFAULT_HIT_CAP,
               interpret: Optional[bool] = None):
    """Jitted standalone wrapper of ``fused_scan_call`` (tests, notebooks)."""
    return fused_scan_call(rows, flo, fhi, alive, coords, first, last,
                           sv, tband, work, hit_cap=hit_cap,
                           interpret=interpret)
