"""Device-resident batch query plane (DESIGN.md §4): the megakernel wave.

The old pipeline ran three device stages per wave (probe → candidate-cell
expansion + bisect → windowed filter) and shipped a (B, N) hit mask back to
the host; the §5 delta/tombstone scan then ran on the host.  This module
replaces all of it with ONE launch per wave of the ``kernels.fused_scan``
megakernel, driven by the per-row candidacy identity (DESIGN.md §4):

    a row is in the numpy path's refined candidate blocks
      ⟺  its cell coordinate lies in the host-probed [first, last] on every
          grid dim  ∧  its sorted attribute lies in [t_lo, t_hi)

so probe + segment search collapse into a branch-free membership test the
kernel evaluates alongside the exact full-predicate filter and the liveness
mask — ``hit = alive ∧ candidate ∧ inside`` — and the nav⊇filter invariant
makes the result bit-identical to numpy.

Frozen per-grid image (``_GridImage``, uploaded once per epoch), every
per-row plane in the kernel's lane layout ``(·, N_pad/128, 128)``
(``kernels.fused_scan.to_lanes``), ``N_pad`` a power of two >= 4096:
  * ``rows``    (D, ·) f32 records, ``+inf``-padded;
  * ``coords``  (k, ·) i32 per-dim cell coordinate of every row (the
    device twin of the directory: mixed-radix decode of each row's cell);
  * ``sv``      (·) f32 in-cell sorted attribute;
  * ``alive``   (·) i32 liveness (tombstones re-uploaded only when the
    tombstone counters move);
  * host f32 edge images (``f32_ceil``/``f32_floor`` paired rounding) for
    the ONE conservative host directory pass per wave that yields
    [first, last] and the ``cells_probed`` stat.

Per wave, every segment — primary grid, outlier grid, and the fixed-shape
delta/tombstone image of the live append log — goes into ONE jitted
``_wave_program`` dispatch (``dispatch_count`` asserts one launch per
wave).  On the Pallas route each grid segment ships a work list
(``_GridImage.work_list``) from the same probe pass: a query's cell box is
a few contiguous row runs (rows are cell-major), and the kernel reads only
the row tiles those runs overlap, ``W`` items a wave at most (``W`` = the
tile count of the plan's largest grid image, a static shape).  A wave
whose list exceeds ``W`` takes the full scan, in the same compiled
program.  Segments with no probe stage (the delta image, single-cell
grids) scan every row, their candidacy being liveness.  Every listed tile
runs the full per-row test, so answers equal the full scan's bit for
bit.  On the CPU-oracle route the grid segments additionally ship
per-query candidate gather-index images (and skew-split into thin/fat
sub-segments, still one dispatch) so per-wave work scales with candidate
counts, not table size — DESIGN.md §4 "CPU oracle fast path".  Outputs
stay device-resident and compacted (per-query hit count +
first ``hit_cap`` hit positions); nothing transfers until ``collect`` — the
explicit drain point (``jax.block_until_ready``) — so a submitted wave can
overlap the previous wave's drain (the executor/server double-buffering
schedule, depth 2).

Overflow contracts (both exact):
  * ``hit_cap``  — detected at DRAIN from the exact device counts.  The
    large pass (``_PlanBase._large_pass``) scans each segment that holds
    an overflowing query again, over the ticket's own segment arrays (its
    images, liveness planes and delta image as submitted, so writes since
    are invisible), and compacts every hit of those queries on the device
    (``compact_range``), ``chunk`` slots a query a pass, ``chunk`` tiered
    from the exact counts (``_hit_chunk``).  ``large_results`` counts the
    queries; a wave without one runs no extra dispatch.
  * ``cell_cap`` — the CPU-oracle route only, whose gather work grows with
    candidate cells: detected at SUBMIT from the host probe; the whole wave
    is answered by the numpy path (``fallbacks`` stat).  The Pallas route
    reads its listed tiles, or every tile when the list does not fit, and
    answers every wave.

The answer is assembled with no sort across the wave (``_assemble``):
each query's ids go to its own stretch of the output, which is sorted
alone.

Shape bucketing: wave width pads to a pow2 bucket (min ``min_bucket``),
grid and delta images to a pow2 row count (min 4096, one kernel word
group), so steady-state serving — and epoch swaps under
background compaction (§5.4), via ``_PlanBase.adopt`` — re-enter compiled
executables; ``compile_count`` exposes the jit cache size for the
regression test.

Epoch versioning (DESIGN.md §5): images freeze ONE snapshot epoch;
compaction swaps the grids, which invalidates the plan by identity
(``COAXIndex`` checks ``plan.primary is self.primary``) — in-flight tickets
keep draining against the frozen images they captured.
"""
from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.gridfile import BatchStats, f32_ceil
from ..core.types import sorted_contains
from ..kernels import ref
from ..kernels._platform import on_cpu, resolve_interpret
from ..kernels.fused_scan import (GROUP_ROWS, WORD_BITS, compact_range,
                                  fused_scan_call, fused_scan_words,
                                  pad_to_lanes, tile_rows, to_lanes)

__all__ = ["DevicePlan", "CoaxDevicePlan", "f32_floor"]

LARGE_TIER = 16          # large-pass hit budgets are hit_cap·16^k ...
LARGE_CHUNK = 1 << 21    # ... slots a query, at most this many a pass


def f32_floor(x: np.ndarray) -> np.ndarray:
    """Largest float32 <= x, elementwise (the mirror of ``gridfile.f32_ceil``)."""
    x = np.asarray(x, dtype=np.float64)
    with np.errstate(over="ignore"):
        y = x.astype(np.float32)
        rounded_up = y.astype(np.float64) > x
        # nextafter past f32 min overflows to -inf — the correct floor there
        return np.where(rounded_up, np.nextafter(y, np.float32(-np.inf)), y)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n - 1).bit_length(), 0) if n > 1 else 1


def _multi_arange(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenate ``[arange(s, s + l) for s, l in zip(starts, lens)]``
    without a Python loop (the candidate-block flattening primitive)."""
    keep = lens > 0
    starts, lens = starts[keep], lens[keep]
    tot = int(lens.sum())
    if not tot:
        return np.empty(0, np.int64)
    step = np.ones(tot, np.int64)
    step[0] = starts[0]
    ends = np.cumsum(lens)[:-1]
    step[ends] = starts[1:] - (starts[:-1] + lens[:-1] - 1)
    return np.cumsum(step)


def _wave_program(segs, config):
    """ONE wave = one dispatch of this jitted program over every segment.

    ``segs`` is a tuple of array dicts (a pytree), ``config`` the matching
    tuple of static per-segment tuples ``(hit_cap, probe, has_sort,
    use_pallas, interpret, gw, nw)``.  Each segment runs the fused
    megakernel (the Pallas kernel on accelerators, its jnp oracle — same
    contract — on CPU) and returns its compacted ``(counts, hits,
    scanned)``.  ``nw > 0`` gives the kernel the segment's ``(2·nw,)`` work
    list of ``(tile, query)`` items, so each query reads only the tiles
    holding its candidate rows (or every tile, when the host marks the list
    as overflowing); ``gw > 0`` restricts the oracle to each query's
    probe-derived candidate rows via a gather-index image.  Both are
    exactness-preserving.
    """
    out = []
    for seg, cfg in zip(segs, config):
        args, kwargs = _scan_args(seg, cfg)
        hit_cap, use_pallas, interpret = cfg[0], cfg[3], cfg[4]
        out.append(
            fused_scan_call(*args, hit_cap=hit_cap, interpret=interpret,
                            **kwargs) if use_pallas
            else ref.fused_scan_ref(*args, hit_cap=hit_cap, **kwargs))
    return tuple(out)


def _words_program(seg, config):
    """The large-answer pass's scan of ONE segment: the same kernel (or
    oracle) and the same inputs as the wave's, returning the uncompacted
    hit words ``(Bp, N/32)`` that ``compact_range`` reads."""
    args, kwargs = _scan_args(seg, config)
    if config[3]:
        return fused_scan_words(*args, interpret=config[4], **kwargs)
    return ref.fused_scan_words_ref(*args, **kwargs)


def _scan_args(seg, config):
    """One segment's operands for the kernel or its oracle: the per-row
    planes and bounds, and the stages its static ``config`` turns on."""
    _, probe, has_sort, use_pallas, _, gw, nw = config
    kwargs = {}
    if probe:
        kwargs.update(coords=seg["coords"], first=seg["first"],
                      last=seg["last"])
    if has_sort:
        kwargs.update(sv=seg["sv"], tband=seg["tband"])
    if use_pallas and nw:
        kwargs["work"] = seg["work"]
    if not use_pallas and gw:
        kwargs["gidx"] = seg["gidx"]
    return (seg["rows"], seg["flo"], seg["fhi"], seg["alive"]), kwargs


def _hit_chunk(count: int, hit_cap: int, n_pad: int) -> int:
    """Slots a query gets in one ``compact_range`` pass for a largest
    answer of ``count`` hits: the first budget ``hit_cap·16^k`` that holds
    it, at most ``LARGE_CHUNK`` and the image's padded rows.  Budgets 16x
    apart keep the compiled shapes few, so answers that vary by tens of
    percent share one; an answer past the chunk takes more passes of the
    same shape."""
    tier = hit_cap * LARGE_TIER
    while tier < count:
        tier *= LARGE_TIER
    return min(tier, LARGE_CHUNK, n_pad)


def _assemble(b: int, small, large):
    """The wave's ``(query_ids, row_ids)``, grouped by query and each
    query's ids ascending, with no sort across the wave.

    ``small`` holds each segment's first-pass hits as ``(query, id)``
    arrays grouped by query; ``large`` holds ``(query, ids)`` blocks from
    the large pass.  Each query's hits are placed in its own stretch of the
    output, then that stretch alone is sorted."""
    total = np.zeros(b, np.int64)
    for q, _ in small:
        total += np.bincount(q, minlength=b)
    for q, ids in large:
        total[q] += ids.size
    start = np.zeros(b + 1, np.int64)
    np.cumsum(total, out=start[1:])
    out = np.empty(int(start[-1]), np.int64)
    fill = start[:-1].copy()
    for q, ids in small:
        if not q.size:
            continue
        cnt = np.bincount(q, minlength=b)
        first = np.cumsum(cnt) - cnt
        out[fill[q] + np.arange(q.size) - first[q]] = ids
        fill += cnt
    for q, ids in large:
        out[fill[q]:fill[q] + ids.size] = ids
        fill[q] += ids.size
    for q in np.nonzero(total > 1)[0]:
        out[start[q]:start[q + 1]].sort()
    return np.repeat(np.arange(b, dtype=np.int64), total), out


class _GridImage:
    """Frozen device image of one ``GridFile`` epoch (uploaded once) plus
    the host-side conservative f32 directory for the per-wave probe."""

    def __init__(self, grid):
        n, k = grid.n_rows, len(grid.grid_dims)
        c = grid.cells_per_dim
        self.grid = grid
        self.n = n
        self.grid_pos = [grid.index_dims.index(d) for d in grid.grid_dims]
        self.sort_pos = (grid.index_dims.index(grid.sort_dim)
                         if grid.sort_dim is not None else None)
        self.has_sort = grid.sort_vals is not None

        edges = (np.stack(grid.inner_edges) if k
                 else np.zeros((0, 0), np.float64))
        self.edges_up_h = f32_ceil(edges).astype(np.float32)
        self.edges_down_h = f32_floor(edges).astype(np.float32)
        # single-cell grids (k == 0 or c == 1) have no probe stage: every
        # live row is a candidate (modulo the sort band)
        self.probe = bool(k and self.edges_up_h.shape[1])
        self.k, self.c = k, c
        self.offsets_h = np.asarray(grid.offsets, np.int64)
        # mixed-radix weights of the row-major cell id, for window bounds
        self._radix = c ** (k - 1 - np.arange(k, dtype=np.int64))

        # pow2 bucket (min one 4096-row kernel group; every pow2 from there
        # is a kernel grid size) with always >= 1 pad row: the gather-list
        # fast path points pad slots at the last (dead, +inf) padded row,
        # which must exist.  Bucketing means epoch-over-epoch growth
        # re-enters compiled wave shapes instead of minting one executable
        # per compaction (§5.4).  Planes are stored in the kernel's lane
        # layout (``fused_scan.to_lanes``).
        n_pad = max(GROUP_ROWS, _next_pow2(n + 1))
        self.n_pad = n_pad
        self.rows_per_tile = tile_rows(n_pad)      # one kernel grid step
        self.tiles = n_pad // self.rows_per_tile
        rows = pad_to_lanes(grid.rows.T, n_pad, np.inf, np.float32)
        self.rows = jnp.asarray(rows)
        self.bytes_resident = rows.nbytes
        del rows
        if self.probe:
            cell_of_row = np.repeat(
                np.arange(grid.n_cells, dtype=np.int64), np.diff(grid.offsets))
            coords = pad_to_lanes(             # row-major decode, dim j digit
                np.stack([(cell_of_row // c ** (k - 1 - j)) % c
                          for j in range(k)]), n_pad, -1, np.int32)
            self.coords = jnp.asarray(coords)
            self.bytes_resident += coords.nbytes
            del coords, cell_of_row
        if self.has_sort:
            sv = pad_to_lanes(grid.sort_vals, n_pad, np.inf, np.float32)
            self.sv = jnp.asarray(sv)
            self.bytes_resident += sv.nbytes
        self.bytes_resident += self.set_alive(None)

    # ------------------------------------------------------------------ #
    def set_alive(self, dead_ids: Optional[np.ndarray]) -> int:
        """(Re)upload the liveness mask — all-live, or ``row_ids`` minus the
        tombstone set.  Returns bytes uploaded."""
        alive = np.zeros(self.n_pad, np.int32)
        if dead_ids is None or not dead_ids.size:
            alive[:self.n] = 1
        else:
            # dead_ids is sorted (``COAXIndex._dead_ids``): binary-search
            # membership, no per-upload re-sort of the 50k-id base
            alive[:self.n] = ~sorted_contains(dead_ids, self.grid.row_ids)
        alive = to_lanes(alive)
        self.alive = jnp.asarray(alive)
        return alive.nbytes

    def probe_batch(self, nav_rects: np.ndarray):
        """ONE host directory pass per wave: per-query per-dim [first, last]
        cell coordinates under the conservative f32 rounding, plus the
        candidate-cell counts reused for the ``cell_cap`` pre-check and the
        ``cells_probed`` stat (previously a second pass)."""
        b = nav_rects.shape[0]
        k = len(self.grid_pos)
        if not self.probe:
            return (np.zeros((b, max(k, 1)), np.int64),
                    np.zeros((b, max(k, 1)), np.int64),
                    np.ones(b, np.int64))
        glo = f32_floor(nav_rects[:, self.grid_pos, 0]).astype(np.float32)
        ghi = f32_ceil(nav_rects[:, self.grid_pos, 1]).astype(np.float32)
        first = np.stack(
            [np.searchsorted(self.edges_up_h[i], glo[:, i], side="right")
             for i in range(k)], axis=1)
        last = np.stack(
            [np.searchsorted(self.edges_down_h[i], ghi[:, i], side="left")
             for i in range(k)], axis=1)
        counts = last - first + 1
        n_cells_q = np.where((counts > 0).all(axis=1),
                             np.maximum(counts, 1).prod(axis=1), 0)
        return first, last, n_cells_q

    def candidate_lists(self, first, last, n_cells_q,
                        qmask: Optional[np.ndarray] = None):
        """Per-query ascending candidate row-position lists, derived from
        the SAME probe pass: every cell in the candidate coord box is one
        contiguous cell-major block ``[offsets[cell], offsets[cell + 1])``,
        enumerated in ascending linear cell id — the exact row set the
        numpy path refines, feeding the oracle's gather fast path
        (``fused_scan_ref``'s ``gidx``)."""
        lists = []
        for q in range(first.shape[0]):
            if n_cells_q[q] <= 0 or (qmask is not None and not qmask[q]):
                lists.append(np.empty(0, np.int64))
                continue
            cells = np.zeros(1, np.int64)
            for j in range(self.k):        # C-order box walk == ascending id
                span = np.arange(first[q, j], last[q, j] + 1) * self._radix[j]
                cells = (cells[:, None] + span[None, :]).ravel()
            starts = self.offsets_h[cells]
            lens = self.offsets_h[cells + 1] - starts
            lists.append(_multi_arange(starts, lens))
        return lists

    def work_list(self, first, last, live: np.ndarray, nw: int) -> np.ndarray:
        """The wave's ``(tile, query)`` work list for the listed kernel, from
        the SAME probe pass, as one flat ``(2·nw,)`` int32 vector: tiles,
        then queries.

        Rows are cell-major, so a query's cell box is one contiguous row
        run per combination of its leading grid dims' cells, each covering
        the last grid dim's span: ``[offsets[c0], offsets[c1 + 1])``.  A
        box of more runs than ``nw`` stands for its whole span
        ``[offsets[first cell], offsets[last cell + 1])``.  Each run turns
        into the tiles it overlaps; items go query by query, tiles ascending
        and unique, and only ``live`` queries have items.  Items past the
        last repeat its tile with query ``-1``.  A list of more than ``nw``
        items is not built: every entry is ``-1``, and the kernel scans
        every tile for every query."""
        work = np.full(2 * nw, -1, np.int32)
        q = np.nonzero(live)[0]
        f, l = first[q].astype(np.int64), last[q].astype(np.int64)
        lead = l[:, :-1] - f[:, :-1] + 1
        runs = lead.prod(axis=1)
        wide = runs > nw
        runs[wide] = 1
        rq = np.repeat(np.arange(q.size), runs)      # run -> live query
        r = np.arange(rq.size) - np.repeat(np.cumsum(runs) - runs, runs)
        c0 = np.zeros(rq.size, np.int64)
        for j in range(self.k - 2, -1, -1):          # last lead dim fastest
            c0 += (f[rq, j] + r % lead[rq, j]) * self._radix[j]
            r //= lead[rq, j]
        c1 = c0 + l[rq, -1]
        c0 += f[rq, -1]
        span = wide[rq]
        c1[span] = l[rq[span]] @ self._radix
        a, e = self.offsets_h[c0], self.offsets_h[c1 + 1]
        keep = e > a
        rq, a, e = rq[keep], a[keep], e[keep]
        t0, t1 = a // self.rows_per_tile, (e - 1) // self.rows_per_tile
        # a query's runs ascend and are disjoint: the next run starts at
        # or past the tile where the last one ended
        dup = np.zeros(rq.size, np.int64)
        dup[1:] = (rq[1:] == rq[:-1]) & (t0[1:] == t1[:-1])
        lens = t1 - t0 + 1 - dup
        n = int(lens.sum())
        if n > nw:
            return work
        tiles = _multi_arange(t0 + dup, lens)
        work[:n] = tiles
        work[n:nw] = tiles[-1] if n else 0
        work[nw:nw + n] = np.repeat(q[rq], lens)
        return work

    def gather_bucket(self, lists) -> int:
        """Static gather width for this wave: the max per-query candidate
        row count, pow2-bucketed (min 512) so steady-state waves share
        compiled shapes; 0 (= full scan) when gathering wouldn't help."""
        if not self.probe:
            return 0
        w = _next_pow2(max(max(l.size for l in lists), 512))
        return 0 if w * 2 >= self.n_pad else w

    def seg_inputs(self, nav_rects, filter_rects, first, last, bp: int,
                   qmask: Optional[np.ndarray] = None,
                   glists=None, gw: int = 0, nw: int = 0):
        """Build this wave's padded per-query device inputs for one segment.

        Padding queries (and ``qmask``-suppressed ones, e.g. the §8.2.3
        outlier bbox skip) are inert: empty probe range and an empty filter
        rect, so they contribute no hits.  When ``gw > 0`` the per-query
        candidate lists ``glists`` ship as a ``(bp, gw)`` gather-index
        image for the oracle's candidate-gather scan (pad slots point at
        the dead ``+inf`` pad row).  When ``nw > 0`` a probe segment ships
        its ``work_list``.  Returns ``(seg dict, uploaded bytes, tiles)``,
        ``tiles`` being ``(tiles the kernel reads, real queries x image
        tiles, whether the list did not fit)`` for a listed segment and
        ``None`` otherwise; the static config tuple comes from
        ``config_for``.
        """
        b = nav_rects.shape[0]
        flo = np.full((bp, filter_rects.shape[1]), np.inf, np.float32)
        fhi = np.full((bp, filter_rects.shape[1]), -np.inf, np.float32)
        flo[:b] = f32_ceil(filter_rects[:, :, 0])
        fhi[:b] = f32_ceil(filter_rects[:, :, 1])
        if qmask is not None:
            flo[:b][~qmask] = np.inf
            fhi[:b][~qmask] = -np.inf
        seg = {"rows": self.rows, "alive": self.alive,
               "flo": jnp.asarray(flo), "fhi": jnp.asarray(fhi)}
        nbytes = flo.size * 8
        tiles = None
        if self.probe:
            k = first.shape[1]
            fa = np.ones((bp, k), np.int32)     # pad: empty range [1, 0]
            la = np.zeros((bp, k), np.int32)
            fa[:b], la[:b] = first, last
            if qmask is not None:
                fa[:b][~qmask], la[:b][~qmask] = 1, 0
            seg["coords"] = self.coords
            seg["first"] = jnp.asarray(fa)
            seg["last"] = jnp.asarray(la)
            nbytes += fa.size * 8
            if gw:
                gi = np.full((bp, gw), self.n_pad - 1, np.int32)
                for q, lst in enumerate(glists):
                    gi[q, :lst.size] = lst[:gw]
                seg["gidx"] = jnp.asarray(gi)
                nbytes += gi.size * 4
            if nw:
                live = (last >= first).all(axis=1)
                if qmask is not None:
                    live &= qmask
                work = self.work_list(first, last, live, nw)
                seg["work"] = jnp.asarray(work)
                nbytes += work.nbytes
                full = bool(work[0] < 0)
                image = b * self.tiles
                tiles = (image if full else int((work[nw:] >= 0).sum()),
                         image, full)
        if self.has_sort:
            tb = np.full((bp, 2), np.inf, np.float32)
            tb[:, 1] = -np.inf                   # pad: empty band [inf, -inf)
            if self.sort_pos is not None:
                tb[:b, 0] = f32_ceil(nav_rects[:, self.sort_pos, 0])
                tb[:b, 1] = f32_ceil(nav_rects[:, self.sort_pos, 1])
            seg["sv"] = self.sv
            seg["tband"] = jnp.asarray(tb)
            nbytes += tb.size * 4
        return seg, nbytes, tiles

    def config_for(self, hit_cap: int, use_pallas: bool, interpret: bool,
                   gw: int = 0, nw: int = 0) -> tuple:
        # the Pallas kernel follows the work list of a probe segment; the
        # gather is the CPU oracle's candidate-scaling lever
        return (hit_cap, self.probe, self.has_sort, use_pallas, interpret,
                0 if use_pallas else int(gw),
                int(nw) if use_pallas and self.probe else 0)


def _extract_hits(counts: np.ndarray, hits: np.ndarray, cap: int):
    """Unpack one segment's compacted device hits: per-query row positions
    for every query whose count fits the buffer (the large pass answers
    the others), grouped by query."""
    take = np.where(counts > cap, 0, counts)
    q = np.repeat(np.arange(take.size), take)
    slot = np.arange(q.size) - np.repeat(np.cumsum(take) - take, take)
    return q, hits[q, slot]


class _PlanBase:
    """Knobs + counters shared by the grid-level and COAX-level plans."""

    def _init_opts(self, cell_cap, min_bucket, hit_cap, use_pallas, interpret):
        self.cell_cap = int(cell_cap)
        self.min_bucket = int(min_bucket)
        self.hit_cap = int(hit_cap)
        self.use_pallas = (not on_cpu()) if use_pallas is None else bool(use_pallas)
        self.interpret = resolve_interpret(interpret)
        # a fresh partial per plan keeps the jit cache (and compile_count)
        # private to this plan instead of shared process-wide
        self._fn = jax.jit(functools.partial(_wave_program),
                           static_argnums=(1,))
        self._words_fn = jax.jit(functools.partial(_words_program),
                                 static_argnums=(1,))
        self._compact_fn = jax.jit(functools.partial(compact_range),
                                   static_argnames=("chunk",))
        self.dispatch_count = 0      # jitted wave-program launches (1/wave)
        self.large_dispatch_count = 0   # large-pass launches (drain time)
        self.bytes_h2d = 0           # resident images + per-wave inputs
        self.bytes_d2h = 0           # drained compacted result buffers

    def adopt(self, other: "_PlanBase") -> None:
        """Carry the previous epoch's jit cache and cumulative counters into
        this fresh plan.  Epoch handoff (§5.4) swaps the grids and rebuilds
        the plan; with pow2-bucketed image shapes the new epoch's waves hit
        the SAME compiled executables, so adopting ``_fn`` keeps
        ``compile_count`` flat across compactions and the launch/transfer
        accounting monotonic."""
        self._fn = other._fn
        self._words_fn = other._words_fn
        self._compact_fn = other._compact_fn
        self.dispatch_count = other.dispatch_count
        self.large_dispatch_count = other.large_dispatch_count
        self.bytes_h2d += other.bytes_h2d
        self.bytes_d2h = other.bytes_d2h

    @property
    def compile_count(self) -> int:
        """Distinct compiled shapes so far, the wave program's and the large
        pass's — the §4 cache-policy metric."""
        return (self.wave_compile_count + int(self._words_fn._cache_size())
                + int(self._compact_fn._cache_size()))

    @property
    def wave_compile_count(self) -> int:
        """Distinct compiled shapes of the wave program alone."""
        return int(self._fn._cache_size())

    def bucket(self, b: int) -> int:
        return max(self.min_bucket, _next_pow2(b))

    def _list_width(self, images) -> int:
        """``W``, the static length of every work list this plan ships: the
        tile count of its largest probe image on the Pallas route (so a
        wave's padding steps cost at most one pass over that image), 0 on
        the CPU-oracle route."""
        if not self.use_pallas:
            return 0
        return max((img.tiles for img in images
                    if img is not None and img.probe), default=0)

    def _count_tiles(self, tiles, sp) -> None:
        """Fold one wave's ``(listed, image, full)`` tile counts of its listed
        segments (``None`` for the others) into the ``device.inputs`` span
        and the global registry (``coax_device_tiles_read_total``; a segment
        whose list did not fit reads its whole image and counts in
        ``coax_device_fullscan_segments_total``)."""
        tiles = [t for t in tiles if t]
        if not tiles:
            return
        listed = sum(t[0] for t in tiles)
        if sp is not None:
            sp.args["tiles_listed"] = listed
            sp.args["tiles_image"] = sum(t[1] for t in tiles)
        g = obs.get_registry()
        g.counter("coax_device_tiles_read_total",
                  "row tiles the wave kernel read").inc(listed)
        full = sum(t[2] for t in tiles)
        if full:
            g.counter("coax_device_fullscan_segments_total",
                      "probe segments whose work list did not fit: every "
                      "tile for every query").inc(full)

    def _over_cell_cap(self, n_cells_q: np.ndarray) -> bool:
        """The CPU-oracle route's submit-time budget (its gather work grows
        with candidate cells).  The Pallas route reads the tiles the probe
        lists, or every tile, so it never declines a wave."""
        return (not self.use_pallas
                and int(n_cells_q.max(initial=0)) > self.cell_cap)

    def _count_h2d(self, nbytes: int) -> None:
        """Fold an upload into the plan counter AND the global registry
        (``coax_device_bytes{direction="h2d"}``, DESIGN.md §10.1).
        ``adopt`` bypasses this: carried bytes were already counted."""
        self.bytes_h2d += nbytes
        obs.get_registry().counter(
            "coax_device_bytes", "bytes moved across the PCIe/ICI boundary",
            ("direction",)).inc(nbytes, direction="h2d")

    def _dispatch(self, segs, config):
        """One jitted wave-program launch.  Telemetry (DESIGN.md §10): the
        ``device.dispatch`` span splits compile from execute — a jit-cache
        miss on this call stamps ``compiled=True`` (and the span's whole
        duration is dominated by XLA compilation; steady-state waves re-
        enter compiled executables and the span is launch cost only).
        Launch count and any compile fold into the global registry."""
        before = self.compile_count
        t0 = time.perf_counter()
        with obs.span("device.dispatch", segs=len(segs)) as sp:
            res = self._fn(tuple(segs), tuple(config))
        compiled = self.compile_count - before
        if sp is not None and compiled:
            sp.args["compiled"] = True
        self.dispatch_count += 1
        g = obs.get_registry()
        g.counter("coax_device_dispatch_total",
                  "jitted wave-program launches").inc()
        if compiled:
            g.counter("coax_device_compile_total",
                      "jit cache misses (new wave shapes)").inc(compiled)
        obs.stage_hist().observe(time.perf_counter() - t0,
                                 stage="dispatch", backend="device")
        return res

    def _large_pass(self, segs, cfgs, seg_np, qmaps):
        """Every hit of each query whose count in a segment exceeds
        ``hit_cap``, from the device (DESIGN.md §4).

        Per such segment: one dispatch of the wave's own scan over the
        ticket's segment arrays (``_words_program``: the images, liveness
        and delta image as submitted, so later writes stay invisible), then
        ``compact_range`` passes over the overflowing queries, ``kb`` of
        them (``bucket``) and ``_hit_chunk`` slots each a pass.  Returns
        ``(segment, query in the segment, hit positions)`` triples and the
        number of wave queries they answer."""
        jobs = [(i, np.nonzero(c > self.hit_cap)[0])
                for i, (c, _, _) in enumerate(seg_np)]
        jobs = [(i, sel) for i, sel in jobs if sel.size]
        if not jobs:
            return [], 0
        out, wave_q = [], set()
        with obs.span("device.large") as sp:
            pending, h2d = [], 0
            for i, sel in jobs:
                counts = seg_np[i][0][sel]
                top = int(counts.max())
                words = self._words_fn(segs[i], cfgs[i])
                chunk = _hit_chunk(top, self.hit_cap,
                                   words.shape[1] * WORD_BITS)
                rows = np.full(self.bucket(sel.size), np.iinfo(np.int32).max,
                               np.int32)        # past the wave: no query
                rows[:sel.size] = sel
                h2d += rows.nbytes
                parts = [self._compact_fn(words, rows, off, chunk=chunk)
                         for off in range(0, top, chunk)]
                self.large_dispatch_count += 1 + len(parts)
                pending.append((i, sel, counts, parts))
                wave_q.update((sel if qmaps[i] is None
                               else qmaps[i][sel]).tolist())
            self._count_h2d(h2d)
            d2h = budget = 0
            for i, sel, counts, parts in pending:
                host = [np.asarray(p) for p in parts]
                d2h += sum(h.nbytes for h in host)
                budget += sum(h.size for h in host)
                pos = host[0] if len(host) == 1 else np.concatenate(host, 1)
                out += [(i, int(q), pos[j, :n])
                        for j, (q, n) in enumerate(zip(sel, counts))]
            self._count_d2h(d2h)
            if sp is not None:
                sp.args.update(queries=len(wave_q),
                               hits=int(sum(c.sum() for _, _, c, _ in pending)),
                               hit_budget=budget, bytes_d2h=d2h)
        return out, len(wave_q)

    def _drain(self, res, bs):
        """Drain point: block, transfer the compacted buffers, count bytes.
        ``bs`` is the real (un-padded) query count per segment.  Returns
        per-segment ``(counts (b,), hits (bp, W), scanned (b,))``.  The
        ``device.transfer`` span covers the ``block_until_ready`` fence
        (``device.wait``: the wave's execution) plus the d2h copies
        (``device.copy``), distinct from the dispatch span's compile+launch
        (DESIGN.md §10.2)."""
        t0 = time.perf_counter()
        d2h = 0
        with obs.span("device.transfer"):
            with obs.span("device.wait"):
                res = jax.block_until_ready(res)
            with obs.span("device.copy") as sp:
                out = []
                for (counts, hits, scanned), b in zip(res, bs):
                    counts = np.asarray(counts)[:b, 0]
                    hits = np.asarray(hits)
                    scanned = np.asarray(scanned)[:b, 0]
                    d2h += counts.nbytes + hits.nbytes + scanned.nbytes
                    out.append((counts, hits, scanned))
                if sp is not None:
                    sp.args["bytes_d2h"] = d2h
        self._count_d2h(d2h)
        obs.stage_hist().observe(time.perf_counter() - t0,
                                 stage="transfer", backend="device")
        return out

    def _count_d2h(self, nbytes: int) -> None:
        self.bytes_d2h += nbytes
        obs.get_registry().counter(
            "coax_device_bytes", "bytes moved across the PCIe/ICI boundary",
            ("direction",)).inc(nbytes, direction="d2h")


class DevicePlan(_PlanBase):
    """Frozen device-resident image of one ``GridFile`` plus its compiled
    megakernel wave program (DESIGN.md §4).

    Parameters
    ----------
    grid : the host ``GridFile`` to freeze (arrays are uploaded once here).
    cell_cap : per-query candidate-cell budget of the CPU-oracle route,
        whose gather work grows with candidate cells: waves where any
        query's directory probe exceeds it return ``None`` from
        ``submit_wave`` so the caller answers them on the numpy path.  The
        Pallas route reads the tiles of each query's work list, or every
        tile when the wave's list does not fit, so it has no such budget
        and answers every wave (§4).
    hit_cap : per-query device hit-buffer budget; queries whose exact count
        exceeds it get all their hits from the drain-time large pass (§4).
    min_bucket : smallest wave bucket; B pads up to ``max(min_bucket,
        next_pow2(B))`` so steady-state widths share compiled shapes.
    use_pallas : route segments through the Pallas kernel; ``None`` picks
        the kernel on real accelerators and the jnp oracle (same contract,
        XLA-compiled) on CPU, where interpret-mode Pallas is a correctness
        tool rather than a fast path.
    """

    def __init__(self, grid, *, cell_cap: int = 256,
                 min_bucket: int = 4, hit_cap: int = 1024,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None):
        self._init_opts(cell_cap, min_bucket, hit_cap, use_pallas, interpret)
        self.grid = grid
        self.epoch = int(getattr(grid, "epoch", 0))   # snapshot version (§5)
        self.n_rows = grid.n_rows
        self._img = _GridImage(grid) if grid.n_rows else None
        if self._img is not None:
            self._count_h2d(self._img.bytes_resident)
        self._nw = self._list_width([self._img])

    # ------------------------------------------------------------------ #
    def submit_wave(self, nav_rects: np.ndarray, filter_rects: np.ndarray):
        """Launch one wave (ONE dispatch); returns an opaque ticket for
        ``collect``, or ``None`` on a CPU-oracle ``cell_cap`` overflow
        (caller answers on numpy).  No results transfer until ``collect``."""
        b = nav_rects.shape[0]
        if b == 0 or self.n_rows == 0:
            return {"b": b, "res": None}
        with obs.span("device.probe"):
            first, last, n_cells_q = self._img.probe_batch(nav_rects)
        if self._over_cell_cap(n_cells_q):
            return None
        bp = self.bucket(b)
        with obs.span("device.inputs") as sp:
            glists, gw = None, 0
            if not self.use_pallas:
                glists = self._img.candidate_lists(first, last, n_cells_q)
                gw = self._img.gather_bucket(glists)
            seg, nbytes, tiles = self._img.seg_inputs(
                nav_rects, filter_rects, first, last, bp,
                glists=glists, gw=gw, nw=self._nw)
            self._count_h2d(nbytes)
            self._count_tiles([tiles], sp)
            if sp is not None:
                sp.args["bytes_h2d"] = nbytes
        cfg = self._img.config_for(self.hit_cap, self.use_pallas,
                                   self.interpret, gw, self._nw)
        res = self._dispatch([seg], [cfg])
        return {"b": b, "res": res, "cells": int(n_cells_q.sum()),
                "seg": seg, "cfg": cfg}

    def collect(self, ticket) -> Tuple[np.ndarray, np.ndarray, dict]:
        """Drain one wave: block, transfer the compacted buffers, answer any
        query over ``hit_cap`` by the large pass, and assemble."""
        b = ticket["b"]
        if ticket["res"] is None:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    {"cells_probed": 0, "rows_scanned": 0,
                     "hit_overflows": 0, "large_results": 0})
        seg_np = self._drain(ticket["res"], [b])
        large, n_large = self._large_pass([ticket["seg"]], [ticket["cfg"]],
                                          seg_np, [None])
        ((counts, hits, scanned),) = seg_np
        with obs.span("device.assemble"):
            ids = self.grid.row_ids
            q, pos = _extract_hits(counts, hits, self.hit_cap)
            out_q, out_r = _assemble(b, [(q, ids[pos])],
                                     [(lq, ids[p]) for _, lq, p in large])
        stats = {"cells_probed": ticket["cells"],
                 "rows_scanned": int(scanned.sum()),
                 "hit_overflows": 0, "large_results": n_large}
        return out_q, out_r, stats

    def run_wave(self, nav_rects: np.ndarray, filter_rects: np.ndarray
                 ) -> Optional[Tuple[np.ndarray, np.ndarray, dict]]:
        """Submit + drain one wave synchronously; ``None`` on a CPU-oracle
        ``cell_cap`` overflow (``GridFile.query_batch`` answers on numpy)."""
        ticket = self.submit_wave(nav_rects, filter_rects)
        if ticket is None:
            return None
        return self.collect(ticket)


class CoaxDevicePlan(_PlanBase):
    """Device wave plan for a whole ``COAXIndex``: primary grid + outlier
    grid + the live delta/tombstone image, fused into ONE dispatch per wave
    (DESIGN.md §4).

    The plan freezes the index's CURRENT epoch grids; write-state (liveness
    masks, delta image) refreshes lazily at submit when the delta-plane
    counters move, by uploading fresh arrays.  Tickets keep the segment
    arrays the wave was dispatched with, so the drain-time large pass
    scans the wave's submit-time snapshot, whatever was written since.

    §9.3 pin retention: an ``EpochPin`` holds a strong reference to the
    plan that was live at pin time, so a compaction's ``adopt()`` of a new
    epoch never drops the jit cache out from under a pinned reader — but
    pinned QUERIES never dispatch through the plan; they run the exact
    host composition over the pin's frozen arrays (``engine.cache``).
    """

    def __init__(self, index, *, cell_cap: int = 256,
                 min_bucket: int = 4, hit_cap: int = 1024,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None):
        self._init_opts(cell_cap, min_bucket, hit_cap, use_pallas, interpret)
        self.index = index
        self.primary = index.primary
        self.outlier = index.outlier
        self.epoch = int(index.epoch)
        self.p_img = _GridImage(self.primary) if self.primary.n_rows else None
        self.o_img = _GridImage(self.outlier) if self.outlier.n_rows else None
        for img in (self.p_img, self.o_img):
            if img is not None:
                self._count_h2d(img.bytes_resident)
        self._nw = self._list_width([self.p_img, self.o_img])
        self._dead_key = None
        self._delta_key = None
        self._delta = None

    def release_images(self) -> None:
        """Drop this plan's device images once its epoch is superseded.
        Tickets of waves already dispatched hold their own references to
        the images (their large pass scans them) until they are
        collected."""
        self.p_img = self.o_img = self._delta = None

    # ------------------------------------------------------------------ #
    def _refresh_writes(self) -> int:
        """Re-upload liveness masks / the delta image iff the delta-plane
        counters moved since the last wave (cheap no-op in steady state).
        Returns the bytes uploaded."""
        dp, do = self.index.delta_primary, self.index.delta_outlier
        dead_key = (dp.n_tombstones, do.n_tombstones)
        nbytes = 0
        if dead_key != self._dead_key:
            dead = self.index._dead_ids()
            for img in (self.p_img, self.o_img):
                if img is not None:
                    nbytes += img.set_alive(dead)
            self._dead_key = dead_key
        delta_key = (dp.n_log, dp.n_log_dead, do.n_log, do.n_log_dead)
        if delta_key != self._delta_key:
            r1, i1 = dp.live_log()
            r2, i2 = do.live_log()
            rows = np.concatenate([r1, r2])
            ids = np.concatenate([i1, i2])
            m = rows.shape[0]
            if m:
                m_pad = max(GROUP_ROWS, _next_pow2(m))   # bounded recompiles
                rows_l = pad_to_lanes(rows.T, m_pad, np.inf, np.float32)
                alive = pad_to_lanes(np.ones(m, np.int32), m_pad, 0, np.int32)
                self._delta = {"rows_l": jnp.asarray(rows_l),
                               "alive": jnp.asarray(alive), "ids": ids}
                nbytes += rows_l.nbytes + alive.nbytes
            else:
                self._delta = None
            self._delta_key = delta_key
        if nbytes:
            self._count_h2d(nbytes)
        return nbytes

    # ------------------------------------------------------------------ #
    def _add_grid_segs(self, img, ids, nav, filt, first, last, ncq,
                       bp: int, out: dict, qmask=None) -> int:
        """Append one grid's wave segment(s) to ``out`` (the in-progress
        dispatch lists).  On the CPU-oracle path the per-query candidate
        lists feed the gather fast path, and a wave whose width budget
        would be set by a few fat queries is SPLIT: a thin segment at the
        median-sized gather width (fat queries inert) plus a fat segment
        over just those queries at a small batch bucket — still one
        dispatch, each query live in exactly one segment (``qmap`` routes
        fat hits back to wave query ids at collect)."""
        b = nav.shape[0]
        glists, gw = None, 0
        if not self.use_pallas:
            glists = img.candidate_lists(first, last, ncq, qmask=qmask)
            gw = img.gather_bucket(glists)
        fat = np.empty(0, np.int64)
        gw_thin = gw
        if gw:
            sizes = np.array([l.size for l in glists])
            gw_thin = _next_pow2(max(512, int(np.median(sizes)) * 2))
            if gw_thin < gw:
                fat = np.nonzero(sizes > gw_thin)[0]
            else:
                gw_thin = gw
        nbytes = 0
        thin_mask = qmask
        thin_lists = glists
        if fat.size:
            thin_mask = np.ones(b, bool) if qmask is None else qmask.copy()
            thin_mask[fat] = False
            thin_lists = [l if m else np.empty(0, np.int64)
                          for l, m in zip(glists, thin_mask)]
        seg, nb, tiles = img.seg_inputs(nav, filt, first, last, bp,
                                        qmask=thin_mask, glists=thin_lists,
                                        gw=gw_thin, nw=self._nw)
        out["segs"].append(seg)
        out["cfgs"].append(img.config_for(self.hit_cap, self.use_pallas,
                                          self.interpret, gw_thin, self._nw))
        out["tiles"].append(tiles)
        out["ids"].append(ids)
        out["qmaps"].append(None)
        out["bs"].append(b)
        nbytes += nb
        if fat.size:
            bp_f = max(self.min_bucket, _next_pow2(fat.size))
            flists = [glists[q] for q in fat]
            gw_f = img.gather_bucket(flists)
            seg, nb, _ = img.seg_inputs(nav[fat], filt[fat], first[fat],
                                        last[fat], bp_f,
                                        glists=flists, gw=gw_f)
            out["segs"].append(seg)
            out["cfgs"].append(img.config_for(
                self.hit_cap, self.use_pallas, self.interpret, gw_f))
            out["ids"].append(ids)
            out["qmaps"].append(fat)
            out["bs"].append(fat.size)
            nbytes += nb
        return nbytes

    def submit_wave(self, nav_rects: np.ndarray, rects: np.ndarray):
        """Launch one COAX wave (ONE dispatch over up to three segments —
        plus thin/fat splits of the grid segments on the CPU-oracle path);
        returns a ticket for ``collect`` or ``None`` on a CPU-oracle
        ``cell_cap`` overflow.  All snapshot/write state the drain needs is captured
        here, synchronously — per-wave snapshot semantics (§5)."""
        b = rects.shape[0]
        if b == 0:
            return {"b": 0, "res": None}
        probe = outlier_probe = None
        with obs.span("device.probe"):
            if self.p_img is not None:
                probe = self.p_img.probe_batch(nav_rects)
            # §8.2.3 bbox skip: non-touch queries go in inert, not
            # sub-batched — same result (no outlier row can pass their
            # predicate), fixed shape
            touch = np.zeros(b, bool)
            if self.index._outlier_lo is not None:
                touch = np.all(
                    (rects[:, :, 0] <= self.index._outlier_hi)
                    & (rects[:, :, 1] > self.index._outlier_lo), axis=1)
            if self.o_img is not None and touch.any():
                # nav == full rect for the full-dim outlier grid
                of, ol, oncq = self.o_img.probe_batch(rects)
                outlier_probe = (of, ol, np.where(touch, oncq, 0))
        cells_probed = 0
        for p in (probe, outlier_probe):
            if p is not None:
                if self._over_cell_cap(p[2]):
                    return None
                cells_probed += int(p[2].sum())

        bp = self.bucket(b)
        out = {"segs": [], "cfgs": [], "ids": [], "qmaps": [], "bs": [],
               "tiles": []}
        with obs.span("device.inputs") as sp:
            nbytes = 0
            up = self._refresh_writes()
            if probe is not None:
                nbytes += self._add_grid_segs(
                    self.p_img, self.primary.row_ids, nav_rects, rects,
                    *probe, bp, out)
            if outlier_probe is not None:
                nbytes += self._add_grid_segs(
                    self.o_img, self.outlier.row_ids, rects, rects,
                    *outlier_probe, bp, out, qmask=touch)
            segs, cfgs, ids_list = out["segs"], out["cfgs"], out["ids"]

            delta = self._delta
            if delta is not None:
                flo = np.full((bp, rects.shape[1]), np.inf, np.float32)
                fhi = np.full((bp, rects.shape[1]), -np.inf, np.float32)
                flo[:b] = f32_ceil(rects[:, :, 0])
                fhi[:b] = f32_ceil(rects[:, :, 1])
                segs.append({"rows": delta["rows_l"],
                             "alive": delta["alive"],
                             "flo": jnp.asarray(flo),
                             "fhi": jnp.asarray(fhi)})
                cfgs.append((self.hit_cap, False, False, self.use_pallas,
                             self.interpret, 0, 0))
                ids_list.append(delta["ids"])
                out["qmaps"].append(None)
                out["bs"].append(b)
                nbytes += flo.size * 8
            self._count_h2d(nbytes)
            self._count_tiles(out["tiles"], sp)
            if sp is not None:
                sp.args["bytes_h2d"] = up + nbytes

        res = self._dispatch(segs, cfgs) if segs else ()
        return {"b": b, "res": res, "ids": ids_list, "cells": cells_probed,
                "qmaps": out["qmaps"], "bs": out["bs"], "segs": segs,
                "cfgs": cfgs}

    # ------------------------------------------------------------------ #
    def collect(self, ticket) -> Tuple[np.ndarray, np.ndarray, BatchStats]:
        """Drain one COAX wave at its explicit drain point and assemble the
        exact ``query_batch`` answer (plus ``BatchStats``)."""
        b = ticket["b"]
        if b == 0 or not ticket["res"]:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    BatchStats(queries=b, backend="device"))
        seg_np = self._drain(ticket["res"], ticket["bs"])
        qmaps = ticket["qmaps"]
        large, n_large = self._large_pass(ticket["segs"], ticket["cfgs"],
                                          seg_np, qmaps)
        with obs.span("device.assemble"):
            small = []
            for (counts, hits, _), ids, qmap in zip(seg_np, ticket["ids"],
                                                    qmaps):
                q, pos = _extract_hits(counts, hits, self.hit_cap)
                small.append((q if qmap is None else qmap[q], ids[pos]))
            blocks = [(lq if qmaps[i] is None else int(qmaps[i][lq]),
                       ticket["ids"][i][p]) for i, lq, p in large]
            out_q, out_r = _assemble(b, small, blocks)
        stats = BatchStats(queries=b, cells_probed=ticket["cells"],
                           rows_scanned=sum(int(s.sum())
                                            for _, _, s in seg_np),
                           backend="device", large_results=n_large)
        return out_q, out_r, stats
