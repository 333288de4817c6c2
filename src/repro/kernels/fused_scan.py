"""Fused probe+search+filter Pallas megakernel (DESIGN.md §4).

One ``pl.pallas_call`` evaluates, for every (query b, record step i) of a
``(Bp, num_steps)`` grid, the WHOLE per-row serving predicate:

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

Layout (what Mosaic lowers).  Per-row planes are stored in the LANE
layout ``(..., N/128, 128)``: each 4096-row group is a ``(32, 128)`` slab
whose element ``(s, l)`` holds logical row ``32·l + s`` (``to_lanes``).
Per-query scalars (bounds, cell ranges, sort band) live whole in SMEM as
flat vectors read at ``program_id(0)``.  Each grid step streams a
``(·, 256, 128)`` block (32768 rows, or the whole image when smaller) and
packs its hit mask into ``(8, 128)`` int32 WORDS by summing ``hit << s``
over each slab's 32 sublanes — so word ``w`` bit ``s`` is logical row
``32·w + s``, and the word vector is the query's hit bitmap in ascending
row order.  Candidate counts accumulate per lane in a resident block.

Compaction happens in XLA, in the same jitted program (``compact_hits``):
popcounts → per-4096-row group counts → a cumsum and a bisection locate the
group, word and bit of each of the first ``hit_cap`` hits.  Outputs are the
exact per-query hit count, the first ``min(count, hit_cap)`` hit positions
ascending, and the candidate-rows-scanned counter.  The bitmap costs
``Bp·N/8`` bytes of HBM writes per wave against the ``Bp·N·4·(D+k+2)``
bytes the scan reads.

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

__all__ = ["fused_scan", "fused_scan_call", "compact_hits", "to_lanes",
           "pad_to_lanes", "from_lanes", "lane_index", "padded_rows",
           "GROUP_ROWS", "STEP_ROWS", "DEFAULT_HIT_CAP"]


def padded_rows(n: int) -> int:
    """Smallest row count >= ``n`` the kernel grid accepts: a multiple of
    ``GROUP_ROWS`` up to one step, of ``STEP_ROWS`` beyond it."""
    unit = GROUP_ROWS if n <= STEP_ROWS else STEP_ROWS
    return max(unit, -(-n // unit) * unit)


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


def _make_kernel(d: int, kk: int, has_sort: bool, groups: int):
    """Kernel body specialised to which predicate stages this segment has.

    Ref order (present refs only):
      SMEM: flo (Bp·D,) f32, fhi (Bp·D,) f32 | first, last (Bp·kk,) i32 |
            tband (Bp·2,) f32
      VMEM: rows (D, S, 128) f32 | coords (kk, S, 128) i32 |
            sv (S, 128) f32 | alive (S, 128) i32
      -> words (G, 128) i32, scanned (G, 128) i32 (resident per query)
    """

    def kernel(*refs):
        it = iter(refs)
        flo_ref, fhi_ref = next(it), next(it)
        first_ref = next(it) if kk else None
        last_ref = next(it) if kk else None
        tband_ref = next(it) if has_sort else None
        rows_ref = next(it)
        coords_ref = next(it) if kk else None
        sv_ref = next(it) if has_sort else None
        alive_ref = next(it)
        words_ref, scanned_ref = next(it), next(it)

        b = pl.program_id(0)
        cand = alive_ref[...] > 0                              # (S, 128)
        for j in range(kk):
            c = coords_ref[j]
            cand &= ((c >= first_ref[b * kk + j])
                     & (c <= last_ref[b * kk + j]))
        if has_sort:
            sv = sv_ref[...]
            cand &= (sv >= tband_ref[2 * b]) & (sv < tband_ref[2 * b + 1])
        hit = cand
        for j in range(d):
            r = rows_ref[j]
            hit &= (r >= flo_ref[b * d + j]) & (r < fhi_ref[b * d + j])

        shape = (groups, WORD_BITS, LANES)
        bit = lax.broadcasted_iota(jnp.int32, shape, 1)
        words_ref[...] = jnp.sum(
            lax.shift_left(hit.astype(jnp.int32).reshape(shape), bit), axis=1)
        n_cand = jnp.sum(cand.astype(jnp.int32).reshape(shape), axis=1)

        @pl.when(pl.program_id(1) == 0)
        def _init():                     # fresh resident accumulator per query
            scanned_ref[...] = jnp.zeros_like(scanned_ref)

        scanned_ref[...] += n_cand

    return kernel


@jax.named_scope("compact_hits")    # in the trace, apart from the kernel
def compact_hits(words, hit_cap: int):
    """Hit bitmaps ``(Bp, N/4096, 128)`` i32 -> ``(counts (Bp, 1) i32,
    hits (Bp, hit_cap) i32)``: each query's true hit count and its first
    ``min(count, hit_cap)`` hit positions ascending, ``-1`` after.

    Word ``w`` bit ``s`` of a row is logical position ``32·w + s``.  The
    j-th hit (1-based ``t``) sits in the first group whose inclusive count
    reaches ``t``; inside it, the word whose running popcount reaches the
    remaining rank, and inside that, the bit whose running count does."""
    pc = lax.population_count(words)                        # (Bp, G, 128)
    grp = jnp.sum(pc, axis=2)                               # (Bp, G)
    cum = jnp.cumsum(grp, axis=1)
    counts = cum[:, -1:]
    tgt = jnp.arange(1, hit_cap + 1, dtype=jnp.int32)
    g = jax.vmap(lambda c: jnp.searchsorted(c, tgt, side="left"))(cum)
    g = jnp.minimum(g, grp.shape[1] - 1).astype(jnp.int32)  # (Bp, cap)
    rank = tgt[None, :] - jnp.take_along_axis(cum - grp, g, axis=1)
    w = jnp.take_along_axis(words, g[:, :, None], axis=1)   # (Bp, cap, 128)
    pcw = lax.population_count(w)
    wcum = jnp.cumsum(pcw, axis=2)
    wi = jnp.minimum(jnp.sum(wcum < rank[..., None], axis=2, keepdims=True),
                     LANES - 1)                             # (Bp, cap, 1)
    rank = rank - jnp.take_along_axis(wcum - pcw, wi, axis=2)[..., 0]
    word = jnp.take_along_axis(w, wi, axis=2)
    bits = (word.astype(jnp.uint32)
            >> jnp.arange(WORD_BITS, dtype=jnp.uint32)) & 1  # (Bp, cap, 32)
    bi = jnp.sum(jnp.cumsum(bits.astype(jnp.int32), axis=2)
                 < rank[..., None], axis=2)
    pos = (g * LANES + wi[..., 0]) * WORD_BITS + bi
    defined = tgt[None, :] <= jnp.minimum(counts, hit_cap)
    return counts, jnp.where(defined, pos, -1).astype(jnp.int32)


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
    *,
    hit_cap: int = DEFAULT_HIT_CAP,
    interpret: Optional[bool] = None,
):
    """Launch the megakernel over one segment and compact its hits; see the
    module docstring.  ``S·128`` must equal ``padded_rows(S·128)``.

    Returns ``(counts (Bp, 1) i32, hits (Bp, hit_cap) i32, scanned (Bp, 1)
    i32)``.  Probe/sort stages are enabled by passing their operands
    (all-or-none per stage).  Not jitted — the device plane embeds this in
    its own jitted wave program; ``fused_scan`` is the standalone entry.
    """
    d, s, _ = rows.shape
    n = s * LANES
    if n != padded_rows(n):
        raise ValueError(f"N={n} rows is not a kernel grid size "
                         f"(padded_rows gives {padded_rows(n)})")
    bp = flo.shape[0]
    step = min(s, STEP_ROWS // LANES)
    groups = step // WORD_BITS
    kk = 0 if coords is None else coords.shape[0]
    has_sort = sv is not None

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands = [flo.reshape(-1), fhi.reshape(-1)]
    in_specs = [smem, smem]
    if kk:
        operands += [first.reshape(-1), last.reshape(-1)]
        in_specs += [smem, smem]
    if has_sort:
        operands.append(tband.reshape(-1))
        in_specs.append(smem)
    operands.append(rows)
    in_specs.append(pl.BlockSpec((d, step, LANES), lambda b, i: (0, i, 0)))
    if kk:
        operands.append(coords)
        in_specs.append(pl.BlockSpec((kk, step, LANES),
                                     lambda b, i: (0, i, 0)))
    plane = pl.BlockSpec((step, LANES), lambda b, i: (i, 0))
    if has_sort:
        operands.append(sv)
        in_specs.append(plane)
    operands.append(alive)
    in_specs.append(plane)

    words, scanned = pl.pallas_call(
        _make_kernel(d, kk, has_sort, groups),
        grid=(bp, s // step),          # steps innermost: resident scanned
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, groups, LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, groups, LANES), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, s // WORD_BITS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((bp, groups, LANES), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
        name="coax_fused_scan",
    )(*operands)
    counts, hits = compact_hits(words, hit_cap)
    return counts, hits, jnp.sum(scanned, axis=(1, 2))[:, None]


@functools.partial(jax.jit, static_argnames=("hit_cap", "interpret"))
def fused_scan(rows, flo, fhi, alive, coords=None, first=None, last=None,
               sv=None, tband=None, *, hit_cap: int = DEFAULT_HIT_CAP,
               interpret: Optional[bool] = None):
    """Jitted standalone wrapper of ``fused_scan_call`` (tests, notebooks)."""
    return fused_scan_call(rows, flo, fhi, alive, coords, first, last,
                           sv, tband, hit_cap=hit_cap, interpret=interpret)
