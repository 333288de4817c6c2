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
wave).  On the CPU-oracle route the grid segments additionally ship
per-query candidate gather-index images (and skew-split into thin/fat
sub-segments, still one dispatch) so per-wave work scales with candidate
counts, not table size — DESIGN.md §4 "CPU oracle fast path".  Outputs
stay device-resident and compacted (per-query hit count +
first ``hit_cap`` hit positions); nothing transfers until ``collect`` — the
explicit drain point (``jax.block_until_ready``) — so a submitted wave can
overlap the previous wave's drain (the executor/server double-buffering
schedule, depth 2).

Overflow contracts (both exact):
  * ``hit_cap``  — detected at DRAIN from the exact device counts; only the
    overflowing queries are re-answered on the host FROM CAPTURED STATE
    (frozen grids + the tombstone set and delta log captured at submit), so
    interleaved writes between submit and drain cannot shift the wave's
    snapshot (``hit_overflows`` stat).  The re-answer must find exactly the
    device's count, or the drain raises.
  * ``cell_cap`` — the CPU-oracle route only, whose gather work grows with
    candidate cells: detected at SUBMIT from the host probe; the whole wave
    is answered by the numpy path (``fallbacks`` stat).  The Pallas route
    scans every row whatever the probe says and answers every wave.

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
from ..kernels.fused_scan import (GROUP_ROWS, fused_scan_call, pad_to_lanes,
                                  to_lanes)

__all__ = ["DevicePlan", "CoaxDevicePlan", "f32_floor"]


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
    use_pallas, interpret, gw)``.  Each segment runs the fused megakernel
    (the Pallas kernel on accelerators, its jnp oracle — same contract — on
    CPU; ``gw > 0`` additionally restricts the oracle to each query's
    probe-derived candidate rows via a gather-index image, an
    exactness-preserving CPU fast path) and returns its compacted
    ``(counts, hits, scanned)``.
    """
    out = []
    for seg, (hit_cap, probe, has_sort, use_pallas, interpret,
              gw) in zip(segs, config):
        kwargs = {}
        if probe:
            kwargs.update(coords=seg["coords"], first=seg["first"],
                          last=seg["last"])
        if has_sort:
            kwargs.update(sv=seg["sv"], tband=seg["tband"])
        if use_pallas:
            out.append(fused_scan_call(
                seg["rows"], seg["flo"], seg["fhi"], seg["alive"],
                hit_cap=hit_cap, interpret=interpret, **kwargs))
        else:
            if gw:
                kwargs["gidx"] = seg["gidx"]
            out.append(ref.fused_scan_ref(
                seg["rows"], seg["flo"], seg["fhi"], seg["alive"],
                hit_cap=hit_cap, **kwargs))
    return tuple(out)


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
                   glists=None, gw: int = 0):
        """Build this wave's padded per-query device inputs for one segment.

        Padding queries (and ``qmask``-suppressed ones, e.g. the §8.2.3
        outlier bbox skip) are inert: empty probe range and an empty filter
        rect, so they contribute no hits.  When ``gw > 0`` the per-query
        candidate lists ``glists`` ship as a ``(bp, gw)`` gather-index
        image for the oracle's candidate-gather scan (pad slots point at
        the dead ``+inf`` pad row).  Returns ``(seg dict, uploaded
        bytes)``; the static config tuple comes from ``config_for``.
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
        if self.has_sort:
            tb = np.full((bp, 2), np.inf, np.float32)
            tb[:, 1] = -np.inf                   # pad: empty band [inf, -inf)
            if self.sort_pos is not None:
                tb[:b, 0] = f32_ceil(nav_rects[:, self.sort_pos, 0])
                tb[:b, 1] = f32_ceil(nav_rects[:, self.sort_pos, 1])
            seg["sv"] = self.sv
            seg["tband"] = jnp.asarray(tb)
            nbytes += tb.size * 4
        return seg, nbytes

    def config_for(self, hit_cap: int, use_pallas: bool, interpret: bool,
                   gw: int = 0) -> tuple:
        # the Pallas kernel path always scans full-N (the accelerator
        # design); the gather is the CPU oracle's candidate-scaling lever
        return (hit_cap, self.probe, self.has_sort,
                use_pallas, interpret, 0 if use_pallas else int(gw))


def _check_reanswer(dev_counts: np.ndarray, host_q: np.ndarray) -> None:
    """The device's per-query counts are exact even past ``hit_cap``; the
    host re-answer of those queries must find exactly as many rows."""
    host = np.bincount(host_q, minlength=dev_counts.size)
    if not np.array_equal(host, dev_counts):
        raise RuntimeError(
            f"device hit counts {dev_counts.tolist()} disagree with the "
            f"host re-answer {host.tolist()}")


def _extract_hits(counts: np.ndarray, hits: np.ndarray, cap: int,
                  over: np.ndarray):
    """Unpack one segment's compacted device hits: per-query row positions
    for every non-overflowing query (overflowers are host re-answered)."""
    take = np.where(over, 0, np.minimum(counts, cap))
    if not take.sum():
        return np.empty(0, np.int64), np.empty(0, np.int64)
    valid = np.arange(cap)[None, :] < take[:, None]
    q, c = np.nonzero(valid)
    return q.astype(np.int64), hits[q, c].astype(np.int64)


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
        self.dispatch_count = 0      # jitted wave-program launches (1/wave)
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
        self.dispatch_count = other.dispatch_count
        self.bytes_h2d += other.bytes_h2d
        self.bytes_d2h = other.bytes_d2h

    @property
    def compile_count(self) -> int:
        """Distinct compiled wave shapes so far — the §4 cache-policy metric."""
        return int(self._fn._cache_size())

    def bucket(self, b: int) -> int:
        return max(self.min_bucket, _next_pow2(b))

    def _over_cell_cap(self, n_cells_q: np.ndarray) -> bool:
        """The CPU-oracle route's submit-time budget (its gather work grows
        with candidate cells).  The Pallas route scans every row whatever
        the probe says, so it never declines a wave."""
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

    def _drain(self, res, bs):
        """Drain point: block, transfer the compacted buffers, count bytes.
        ``bs`` is the real (un-padded) query count per segment.  Returns
        per-segment ``(counts (b,), hits (bp, W), scanned (b,))``.  The
        ``device.transfer`` span covers the ``block_until_ready`` fence
        plus the d2h copies — execute+transfer time, distinct from the
        dispatch span's compile+launch (DESIGN.md §10.2)."""
        t0 = time.perf_counter()
        d2h = 0
        with obs.span("device.transfer") as sp:
            res = jax.block_until_ready(res)
            out = []
            for (counts, hits, scanned), b in zip(res, bs):
                counts = np.asarray(counts)[:b, 0]
                hits = np.asarray(hits)
                scanned = np.asarray(scanned)[:b, 0]
                d2h += counts.nbytes + hits.nbytes + scanned.nbytes
                out.append((counts, hits, scanned))
            if sp is not None:
                sp.args["bytes_d2h"] = d2h
        self.bytes_d2h += d2h
        obs.get_registry().counter(
            "coax_device_bytes", "bytes moved across the PCIe/ICI boundary",
            ("direction",)).inc(d2h, direction="d2h")
        obs.stage_hist().observe(time.perf_counter() - t0,
                                 stage="transfer", backend="device")
        return out


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
        Pallas route scans every row whatever the probe says, so it has no
        such budget and answers every wave (§4).
    hit_cap : per-query device hit-buffer budget; queries whose exact count
        exceeds it are re-answered on the host at drain time (§4).
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

    # ------------------------------------------------------------------ #
    def submit_wave(self, nav_rects: np.ndarray, filter_rects: np.ndarray):
        """Launch one wave (ONE dispatch); returns an opaque ticket for
        ``collect``, or ``None`` on a CPU-oracle ``cell_cap`` overflow
        (caller answers on numpy).  No results transfer until ``collect``."""
        b = nav_rects.shape[0]
        if b == 0 or self.n_rows == 0:
            return {"b": b, "res": None}
        first, last, n_cells_q = self._img.probe_batch(nav_rects)
        if self._over_cell_cap(n_cells_q):
            return None
        bp = self.bucket(b)
        glists, gw = None, 0
        if not self.use_pallas:
            glists = self._img.candidate_lists(first, last, n_cells_q)
            gw = self._img.gather_bucket(glists)
        seg, nbytes = self._img.seg_inputs(nav_rects, filter_rects,
                                           first, last, bp,
                                           glists=glists, gw=gw)
        cfg = self._img.config_for(self.hit_cap, self.use_pallas,
                                   self.interpret, gw)
        res = self._dispatch([seg], [cfg])
        self._count_h2d(nbytes)
        return {"b": b, "res": res, "cells": int(n_cells_q.sum()),
                "nav": nav_rects, "filt": filter_rects}

    def collect(self, ticket) -> Tuple[np.ndarray, np.ndarray, dict]:
        """Drain one wave: block, transfer the compacted buffers, unpack,
        and host re-answer any ``hit_cap`` overflowers from the frozen grid."""
        b = ticket["b"]
        if ticket["res"] is None:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    {"cells_probed": 0, "rows_scanned": 0, "hit_overflows": 0})
        ((counts, hits, scanned),) = self._drain(ticket["res"], [b])
        over = counts > self.hit_cap
        q, pos = _extract_hits(counts, hits, self.hit_cap, over)
        out_q, out_r = q, self.grid.row_ids[pos]
        rows_scanned = int(scanned.sum())
        if over.any():                # exact per-query host re-answer (§4)
            qsel = np.nonzero(over)[0]
            qo, ro = self.grid._query_batch_numpy(
                ticket["nav"][qsel], ticket["filt"][qsel])
            _check_reanswer(counts[qsel], qo)
            rows_scanned += self.grid.last_batch_stats.rows_scanned
            out_q = np.concatenate([out_q, qsel[qo]])
            out_r = np.concatenate([out_r, ro])
        order = np.lexsort((out_r, out_q))
        stats = {"cells_probed": ticket["cells"],
                 "rows_scanned": rows_scanned,
                 "hit_overflows": int(over.sum())}
        return out_q[order], out_r[order], stats

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
    counters move.  Tickets capture every host array a drain-time re-answer
    needs, so collecting after further writes still answers from the wave's
    submit-time snapshot.

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
        self._dead_key = None
        self._dead_host = np.empty(0, np.int64)
        self._delta_key = None
        self._delta = None

    def release_images(self) -> None:
        """Drop this plan's device images once its epoch is superseded.
        Waves already dispatched hold their own references until they
        finish, and ``collect`` never reads the images."""
        self.p_img = self.o_img = self._delta = None

    # ------------------------------------------------------------------ #
    def _refresh_writes(self) -> None:
        """Re-upload liveness masks / the delta image iff the delta-plane
        counters moved since the last wave (cheap no-op in steady state)."""
        dp, do = self.index.delta_primary, self.index.delta_outlier
        dead_key = (dp.n_tombstones, do.n_tombstones)
        if dead_key != self._dead_key:
            self._dead_host = self.index._dead_ids()
            for img in (self.p_img, self.o_img):
                if img is not None:
                    self._count_h2d(img.set_alive(self._dead_host))
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
                               "alive": jnp.asarray(alive),
                               "rows": rows, "ids": ids}
                self._count_h2d(rows_l.nbytes + alive.nbytes)
            else:
                self._delta = None
            self._delta_key = delta_key

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
        seg, nb = img.seg_inputs(nav, filt, first, last, bp,
                                 qmask=thin_mask, glists=thin_lists,
                                 gw=gw_thin)
        out["segs"].append(seg)
        out["cfgs"].append(img.config_for(self.hit_cap, self.use_pallas,
                                          self.interpret, gw_thin))
        out["ids"].append(ids)
        out["qmaps"].append(None)
        out["bs"].append(b)
        nbytes += nb
        if fat.size:
            bp_f = max(self.min_bucket, _next_pow2(fat.size))
            flists = [glists[q] for q in fat]
            gw_f = img.gather_bucket(flists)
            seg, nb = img.seg_inputs(nav[fat], filt[fat], first[fat],
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
        self._refresh_writes()
        bp = self.bucket(b)
        out = {"segs": [], "cfgs": [], "ids": [], "qmaps": [], "bs": []}
        cells_probed = 0
        nbytes = 0

        if self.p_img is not None:
            first, last, ncq = self.p_img.probe_batch(nav_rects)
            if self._over_cell_cap(ncq):
                return None
            cells_probed += int(ncq.sum())
            nbytes += self._add_grid_segs(self.p_img, self.primary.row_ids,
                                          nav_rects, rects, first, last,
                                          ncq, bp, out)

        # §8.2.3 bbox skip: non-touch queries go in inert, not sub-batched —
        # same result (no outlier row can pass their predicate), fixed shape
        touch = np.zeros(b, bool)
        if self.index._outlier_lo is not None:
            touch = np.all(
                (rects[:, :, 0] <= self.index._outlier_hi)
                & (rects[:, :, 1] > self.index._outlier_lo), axis=1)
        if self.o_img is not None and touch.any():
            # nav == full rect for the full-dim outlier grid
            of, ol, oncq = self.o_img.probe_batch(rects)
            oncq = np.where(touch, oncq, 0)
            if self._over_cell_cap(oncq):
                return None
            cells_probed += int(oncq.sum())
            nbytes += self._add_grid_segs(self.o_img, self.outlier.row_ids,
                                          rects, rects, of, ol, oncq, bp,
                                          out, qmask=touch)
        segs, cfgs, ids_list = out["segs"], out["cfgs"], out["ids"]

        delta = self._delta
        if delta is not None:
            flo = np.full((bp, rects.shape[1]), np.inf, np.float32)
            fhi = np.full((bp, rects.shape[1]), -np.inf, np.float32)
            flo[:b] = f32_ceil(rects[:, :, 0])
            fhi[:b] = f32_ceil(rects[:, :, 1])
            segs.append({"rows": delta["rows_l"], "alive": delta["alive"],
                         "flo": jnp.asarray(flo), "fhi": jnp.asarray(fhi)})
            cfgs.append((self.hit_cap, False, False, self.use_pallas,
                         self.interpret, 0))
            ids_list.append(delta["ids"])
            out["qmaps"].append(None)
            out["bs"].append(b)
            nbytes += flo.size * 8

        res = self._dispatch(segs, cfgs) if segs else ()
        self._count_h2d(nbytes)
        return {"b": b, "res": res, "ids": ids_list, "cells": cells_probed,
                "qmaps": out["qmaps"], "bs": out["bs"],
                "nav": nav_rects, "rects": rects, "touch": touch,
                "dead": self._dead_host,
                "delta": None if delta is None
                else (delta["rows"], delta["ids"])}

    # ------------------------------------------------------------------ #
    def collect(self, ticket) -> Tuple[np.ndarray, np.ndarray, BatchStats]:
        """Drain one COAX wave at its explicit drain point and assemble the
        exact ``query_batch`` answer (plus ``BatchStats``)."""
        b = ticket["b"]
        if b == 0 or not ticket["res"]:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    BatchStats(queries=b, backend="device"))
        seg_np = self._drain(ticket["res"], ticket["bs"])
        over = np.zeros(b, bool)
        total = np.zeros(b, np.int64)          # exact device count per query
        rows_scanned = 0
        for (counts, _, scanned), qmap in zip(seg_np, ticket["qmaps"]):
            o = counts > self.hit_cap
            if qmap is None:
                over |= o
                total += counts
            else:
                over[qmap[o]] = True
                total[qmap] += counts
            rows_scanned += int(scanned.sum())
        parts_q, parts_r = [], []
        for (counts, hits, _), ids, qmap in zip(seg_np, ticket["ids"],
                                                ticket["qmaps"]):
            q, pos = _extract_hits(counts, hits, self.hit_cap,
                                   over if qmap is None else over[qmap])
            parts_q.append(q if qmap is None else qmap[q])
            parts_r.append(ids[pos])
        n_over = int(over.sum())
        if n_over:
            qsel = np.nonzero(over)[0]
            qo, ro, extra = self._reanswer(ticket, qsel)
            _check_reanswer(total[qsel], qo)
            parts_q.append(qsel[qo])
            parts_r.append(ro)
            rows_scanned += extra
        out_q = np.concatenate(parts_q)
        out_r = np.concatenate(parts_r)
        order = np.lexsort((out_r, out_q))
        stats = BatchStats(queries=b, cells_probed=ticket["cells"],
                           rows_scanned=rows_scanned, backend="device",
                           hit_overflows=n_over)
        return out_q[order], out_r[order], stats

    def _reanswer(self, ticket, qsel: np.ndarray):
        """Exact host answer for ``hit_cap``-overflowing queries, replayed
        from the ticket's CAPTURED state (frozen epoch grids + the tombstone
        set and delta log as of submit) — writes applied between submit and
        drain are invisible, preserving per-wave snapshot semantics."""
        nav = ticket["nav"][qsel]
        rects = ticket["rects"][qsel]
        q_p, r_p = self.primary._query_batch_numpy(nav, rects)
        extra = self.primary.last_batch_stats.rows_scanned
        touch = ticket["touch"][qsel]
        if touch.any() and self.outlier.n_rows:
            sub = rects[touch]
            q_o, r_o = self.outlier._query_batch_numpy(sub, sub)
            extra += self.outlier.last_batch_stats.rows_scanned
            if r_o.size:
                q_p = np.concatenate([q_p, np.nonzero(touch)[0][q_o]])
                r_p = np.concatenate([r_p, r_o])
        dead = ticket["dead"]
        if dead.size and r_p.size:
            keep = ~sorted_contains(dead, r_p)
            q_p, r_p = q_p[keep], r_p[keep]
        if ticket["delta"] is not None:
            drows, dids = ticket["delta"]
            rows64 = drows.astype(np.float64)      # exact f64 upcast compare
            hit = np.ones((qsel.size, dids.size), bool)
            for j in range(drows.shape[1]):
                v = rows64[:, j]
                np.logical_and(hit, v[None, :] >= rects[:, j, 0][:, None],
                               out=hit)
                np.logical_and(hit, v[None, :] < rects[:, j, 1][:, None],
                               out=hit)
            qd, pos = np.nonzero(hit)
            q_p = np.concatenate([q_p, qd.astype(np.int64)])
            r_p = np.concatenate([r_p, dids[pos]])
            extra += int(qsel.size) * int(dids.size)
        return q_p, r_p, int(extra)
