"""Device-resident serving plane (DESIGN.md §4): device/numpy equivalence.

The contract under test: ``backend="device"`` returns EXACTLY the numpy
path's ``(query_ids, row_ids)`` on every workload — including waves that
overflow the candidate-cell cap and fall back to numpy — and steady-state
serving compiles at most once per ``(bucket_B, padded_N, D)`` shape.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (COAXIndex, GridFile, full_rect, point_rect)
from repro.data import make_airline, make_osm
from repro.engine import BatchQueryExecutor, QueryServer, split_hits
from workloads import engine_workload, engine_workloads, rects_for


@pytest.mark.parametrize("name,ds", engine_workloads(),
                         ids=lambda w: w if isinstance(w, str) else "")
def test_device_equals_numpy_and_scalar(name, ds):
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data)
    q_n, r_n = idx.query_batch(rects)
    idx.backend = "device"
    q_d, r_d = idx.query_batch(rects)
    assert np.array_equal(q_d, q_n), name
    assert np.array_equal(r_d, r_n), name
    assert np.all(np.diff(q_d) >= 0)
    per_query = split_hits(q_d, r_d, rects.shape[0])
    idx.backend = "numpy"
    for i, r in enumerate(rects):
        assert np.array_equal(per_query[i], idx.query(r)), (name, i)


@pytest.mark.parametrize("sort_dim", [None, 0, 2])
def test_gridfile_device_equals_numpy(sort_dim):
    rng = np.random.default_rng(4)
    data = rng.normal(0, 10, (6_000, 3)).astype(np.float32)
    gf = GridFile(data, index_dims=[0, 1, 2], cells_per_dim=5,
                  sort_dim=sort_dim, backend="device")
    rects = np.sort(rng.uniform(-20, 20, (40, 3, 2)), axis=-1)
    rects[0] = full_rect(3)
    q_d, r_d = gf.query_batch(rects, rects)
    gf.backend = "numpy"
    q_n, r_n = gf.query_batch(rects, rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n), sort_dim


def test_device_pallas_kernel_path():
    """The same pipeline with the Pallas kernel (interpret mode) slotted in
    for step 5 instead of the jnp oracle — identical results."""
    rng = np.random.default_rng(7)
    data = rng.normal(0, 10, (1_500, 3)).astype(np.float32)
    rects = np.sort(rng.uniform(-20, 20, (8, 3, 2)), axis=-1)
    rects[0] = full_rect(3)
    gf = GridFile(data, index_dims=[0, 1, 2], cells_per_dim=4, sort_dim=1,
                  backend="device",
                  device_opts={"use_pallas": True, "interpret": True})
    q_d, r_d = gf.query_batch(rects, rects)
    gf.backend = "numpy"
    q_n, r_n = gf.query_batch(rects, rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)


def test_device_empty_batch_and_empty_index():
    ds = make_airline(5_000, seed=1)
    idx = COAXIndex(ds.data, backend="device")
    q, r = idx.query_batch(np.zeros((0, ds.data.shape[1], 2)))
    assert q.size == 0 and r.size == 0
    gf = GridFile(np.empty((0, 2), np.float32), index_dims=[0, 1],
                  cells_per_dim=3, backend="device")
    q, r = gf.query_batch(full_rect(2)[None], full_rect(2)[None])
    assert q.size == 0 and r.size == 0


def test_device_all_outlier_queries():
    """Point queries aimed only at outlier rows: the primary probe returns
    nothing, every hit flows through the outlier grid's device plan."""
    ds = engine_workload("generic_fd")
    idx = COAXIndex(ds.data)
    assert idx.outlier.n_rows > 0
    o_rows = ds.data[idx.outlier.row_ids[:12]]
    rects = np.stack([point_rect(p) for p in o_rows])
    q_n, r_n = idx.query_batch(rects)
    assert r_n.size >= rects.shape[0]          # every target row is a hit
    idx.backend = "device"
    q_d, r_d = idx.query_batch(rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)


def test_device_f32_range_bounds():
    """Rect bounds beyond float32 range exercise the f32_ceil/f32_floor
    +-inf padding interplay: +-1e39 must behave like +-inf, and bounds just
    inside f32 range must not round across any record value."""
    ds = make_airline(8_000, seed=2)
    d = ds.data.shape[1]
    idx = COAXIndex(ds.data)
    rects = np.stack([
        np.stack([np.full(d, -1e39), np.full(d, 1e39)], axis=-1),   # ~full
        np.stack([np.full(d, 1e38), np.full(d, 1e39)], axis=-1),    # empty
        np.stack([np.full(d, -1e39), ds.data[0].astype(np.float64)], axis=-1),
        point_rect(ds.data[3]),
    ])
    q_n, r_n = idx.query_batch(rects)
    assert split_hits(q_n, r_n, 4)[0].size == ds.data.shape[0]      # full hit
    idx.backend = "device"
    q_d, r_d = idx.query_batch(rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)


def test_overflow_fallback_matches_numpy():
    """cell_cap=1 forces every multi-cell wave back to the numpy path; the
    contract (identical hits) must hold across the fallback seam."""
    rng = np.random.default_rng(9)
    data = rng.normal(0, 10, (4_000, 3)).astype(np.float32)
    rects = np.sort(rng.uniform(-20, 20, (16, 3, 2)), axis=-1)
    gf = GridFile(data, index_dims=[0, 1, 2], cells_per_dim=5, sort_dim=1,
                  backend="device", device_opts={"cell_cap": 1})
    q_d, r_d = gf.query_batch(rects, rects)
    assert gf.last_batch_stats.fallbacks == 1
    assert gf.last_batch_stats.backend == "numpy"
    gf.backend = "numpy"
    q_n, r_n = gf.query_batch(rects, rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)


def test_compile_cache_and_bucketed_shapes():
    """Steady-state serving compiles at most once per (bucket_B, N, D):
    repeated same-width waves reuse one executable; a single execute() call
    spanning two wave widths (8 + 4) compiles exactly two shapes."""
    rng = np.random.default_rng(11)
    data = rng.normal(0, 10, (6_000, 3)).astype(np.float32)
    gf = GridFile(data, index_dims=[0, 1, 2], cells_per_dim=4, sort_dim=2,
                  backend="device")
    rects = np.sort(rng.uniform(-20, 20, (12, 3, 2)), axis=-1)

    ex = BatchQueryExecutor(gf_wrap(gf), max_batch=8, backend="device")
    plan = gf.device_plan
    assert plan is not None
    for _ in range(3):                       # repeated same-shape waves
        ex.execute(rects[:8])
    assert plan.wave_compile_count == 1, "steady-state wave recompiled"
    # rect 1 holds more rows than hit_cap: one large-pass scan and one
    # compaction shape, compiled once
    assert ex.stats()["large_results"] == 3
    assert plan.compile_count == 3

    got = ex.execute(rects)                  # one call, waves of 8 and 4
    assert plan.wave_compile_count == 2, \
        "second bucket shape should compile once"
    for _ in range(2):
        ex.execute(rects)
    assert plan.wave_compile_count == 2, "repeat waves must hit the jit cache"
    assert plan.compile_count == 4

    gf.backend = "numpy"
    for i, r in enumerate(rects):
        assert np.array_equal(got[i], gf.query(r, r)), i


def gf_wrap(gf):
    """Adapter giving a raw GridFile the (rects,)-shaped query_batch the
    executor drives (nav == filter), plus backend passthrough."""
    class _W:
        backend = property(lambda s: gf.backend,
                           lambda s, v: setattr(gf, "backend", v))

        def query_batch(self, rects):
            return gf.query_batch(rects, rects)

        @property
        def last_batch_stats(self):
            return gf.last_batch_stats
    return _W()


def test_executor_and_server_device_plumbing():
    ds = make_osm(8_000, seed=5)
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data, n=10, seed=3)[:10]
    ex = BatchQueryExecutor(idx, max_batch=4, backend="device")
    assert idx.backend == "device" and ex.backend == "device"
    got = ex.execute(rects)
    s = ex.stats()
    assert s["backend"] == "device"
    assert s["rows_scanned"] > 0 and s["cells_probed"] > 0
    assert any(w.backend == "device" for w in ex.wave_stats)

    srv = QueryServer(COAXIndex(ds.data), max_batch=4, backend="device")
    qids = srv.submit_many(rects)
    results = srv.drain()
    idx.backend = "numpy"
    for qid, r, g in zip(qids, rects, got):
        assert np.array_equal(results[qid], g)
        assert np.array_equal(g, idx.query(r))


def test_executor_backend_validation():
    from repro.core import FullScan
    ds = make_airline(2_000, seed=0)
    with pytest.raises(ValueError):
        BatchQueryExecutor(FullScan(ds.data), backend="device")
    ex = BatchQueryExecutor(FullScan(ds.data), backend="numpy")
    assert ex.backend == "numpy"


# --------------------------------------------------------------------- #
# Fused megakernel (DESIGN.md §4): interpret-mode parity vs the oracles
# --------------------------------------------------------------------- #
def test_fused_kernel_interpret_parity():
    """The Pallas megakernel in interpret mode vs the jnp oracle vs the
    shipped batch-scan oracle, across every stage combination — counts,
    compacted hit prefixes and rows-scanned must agree exactly."""
    from repro.kernels import fused_range_scan
    from repro.kernels import ref as kref
    import jax.numpy as jnp

    rng = np.random.default_rng(21)
    n, d, b, cap = 700, 3, 5, 64
    rows_t = rng.normal(0, 10, (d, n)).astype(np.float32)
    lo = rng.uniform(-15, 0, (b, d)).astype(np.float32)
    hi = lo + rng.uniform(0, 20, (b, d)).astype(np.float32)
    alive = (rng.random(n) > 0.1).astype(np.int32)
    coords = rng.integers(0, 4, (2, n)).astype(np.int32)
    first = rng.integers(0, 2, (b, 2)).astype(np.int32)
    last = first + rng.integers(0, 3, (b, 2)).astype(np.int32)
    sv = rows_t[1]
    tband = np.stack([lo[:, 1], hi[:, 1]], axis=1)

    stage_sets = [{}, {"coords": coords, "first": first, "last": last},
                  {"sv": sv, "tband": tband},
                  {"coords": coords, "first": first, "last": last,
                   "sv": sv, "tband": tband}]
    for stages in stage_sets:
        outs = [fused_range_scan(rows_t, lo, hi, alive, **stages,
                                 hit_cap=cap, use_pallas=up)
                for up in (True, False)]
        for (c_a, h_a, s_a), (c_b, h_b, s_b) in zip(outs, outs[1:]):
            assert np.array_equal(c_a, c_b), stages.keys()
            assert np.array_equal(s_a, s_b), stages.keys()
            # hit buffers agree on the defined prefix (rest unspecified)
            take = np.minimum(np.asarray(c_a), cap)
            for q in range(b):
                assert np.array_equal(np.asarray(h_a)[q, :take[q]],
                                      np.asarray(h_b)[q, :take[q]])

        # brute-force ground truth for the full predicate + stages
        inside = np.all((rows_t[None] >= lo[:, :, None])
                        & (rows_t[None] < hi[:, :, None]), axis=1)
        cand = np.broadcast_to(alive > 0, (b, n)).copy()
        if "coords" in stages:
            cand &= np.all((coords[None] >= first[:, :, None])
                           & (coords[None] <= last[:, :, None]), axis=1)
        if "sv" in stages:
            cand &= (sv[None] >= tband[:, :1]) & (sv[None] < tband[:, 1:])
        hit = cand & inside
        counts, hits, scanned = outs[0]
        assert np.array_equal(np.asarray(counts), hit.sum(axis=1))
        assert np.array_equal(np.asarray(scanned), cand.sum(axis=1))
        for q in range(b):
            want = np.nonzero(hit[q])[0][:min(int(counts[q]), cap)]
            assert np.array_equal(np.asarray(hits)[q, :want.size], want)

    # cross-check counts against the shipped batch-scan oracle (no stages)
    win = jnp.broadcast_to(jnp.array([0, n], jnp.int32), (b, 2))
    pad = 256 - (n % 256)
    padded = jnp.pad(jnp.asarray(rows_t), ((0, 0), (0, pad)),
                     constant_values=jnp.inf)
    _, ref_counts = kref.range_scan_batch_ref(
        padded, jnp.asarray(lo).T, jnp.asarray(hi).T, win, tile=256)
    c0, _, _ = fused_range_scan(rows_t, lo, hi, hit_cap=cap,
                                use_pallas=True)
    assert np.array_equal(np.asarray(c0), np.asarray(ref_counts.sum(axis=1)))


def test_gather_oracle_matches_full_scan():
    """The CPU oracle's candidate-gather scan (per-query ``gidx`` row
    lists) is bit-identical to the full-array scan whenever the lists
    cover each query's candidate coord box — the probe-derived contract
    the device plans rely on (cell-major rows, one contiguous block per
    box cell, pad slots pointing at a dead pad row)."""
    from repro.engine.device import _multi_arange
    from repro.kernels import ref as kref
    from repro.kernels.fused_scan import pad_to_lanes, padded_rows

    rng = np.random.default_rng(33)
    n, d, b, k, c = 2_040, 3, 7, 2, 4
    cap = 64
    # cell-major layout: rows sorted by linear cell id, like a GridFile,
    # then one dead +inf pad row for the gather lists to point at
    cell = np.sort(rng.integers(0, c ** k, n))
    coords = np.stack([(cell // c ** (k - 1 - j)) % c for j in range(k)])
    coords = np.pad(coords, ((0, 0), (0, 1)),
                    constant_values=-1).astype(np.int32)
    offsets = np.searchsorted(cell, np.arange(c ** k + 1))
    rows_t = rng.normal(0, 10, (d, n)).astype(np.float32)
    rows_t = np.pad(rows_t, ((0, 0), (0, 1)), constant_values=np.inf)
    alive = np.append((rng.random(n) > 0.1), 0).astype(np.int32)
    lo = rng.uniform(-15, 0, (b, d)).astype(np.float32)
    hi = lo + rng.uniform(0, 25, (b, d)).astype(np.float32)
    first = rng.integers(0, c - 1, (b, k)).astype(np.int32)
    last = first + rng.integers(0, 2, (b, k)).astype(np.int32)
    radix = c ** (k - 1 - np.arange(k))
    lists = []
    for q in range(b):
        cells = (first[q][None, :] +
                 np.stack(np.meshgrid(*[np.arange(last[q, j] - first[q, j] + 1)
                                        for j in range(k)], indexing="ij"),
                          axis=-1).reshape(-1, k)) @ radix
        cells.sort()
        lists.append(_multi_arange(offsets[cells],
                                   offsets[cells + 1] - offsets[cells]))
    gw = 1 << int(max(max(l.size for l in lists), 1) - 1).bit_length()
    assert 0 < gw < n
    gidx = np.full((b, gw), n, np.int32)           # pad -> the dead pad row
    for q, lst in enumerate(lists):
        gidx[q, :lst.size] = lst

    # the kernel's lane layout, dead-padded to its grid size
    n_pad = padded_rows(n + 1)
    rows_l = pad_to_lanes(rows_t, n_pad, np.inf, np.float32)
    coords_l = pad_to_lanes(coords, n_pad, -1, np.int32)
    alive_l = pad_to_lanes(alive, n_pad, 0, np.int32)
    full = kref.fused_scan_ref(rows_l, lo, hi, alive_l, coords_l,
                               first, last, hit_cap=cap)
    gath = kref.fused_scan_ref(rows_l, lo, hi, alive_l, coords_l,
                               first, last, gidx=np.asarray(gidx),
                               hit_cap=cap)
    c_f, h_f, s_f = (np.asarray(x) for x in full)
    c_g, h_g, s_g = (np.asarray(x) for x in gath)
    assert np.array_equal(c_f, c_g)
    assert np.array_equal(s_f, s_g)
    for q in range(b):
        take = min(int(c_f[q, 0]), cap)
        assert np.array_equal(h_f[q, :take], h_g[q, :take])
        assert (h_g[q, take:] == -1).all()


def test_hit_cap_overflow_reanswer_matches_numpy():
    """A tiny hit buffer sends queries to the drain-time large pass, which
    answers them on the device: results stay bit-identical, nothing is
    answered again on the host, and the large answers are counted."""
    ds = make_airline(6_000, seed=4)
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data, n=10, seed=5)     # includes a full-range rect
    q_n, r_n = idx.query_batch(rects)
    idx_d = COAXIndex(ds.data, backend="device",
                      device_opts={"hit_cap": 16})
    q_d, r_d = idx_d.query_batch(rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)
    st = idx_d.last_batch_stats
    assert st.backend == "device"                        # not a wave fallback
    assert st.hit_overflows == 0
    sizes = np.bincount(q_n, minlength=rects.shape[0])
    assert st.large_results == int((sizes > 16).sum()) > 0
    # the delta segment's own answers past the cap take the pass too
    extra = make_airline(200, seed=9).data
    for i in (idx, idx_d):
        i.insert(extra)
    q_n, r_n = idx.query_batch(rects)
    q_d, r_d = idx_d.query_batch(rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)
    assert idx_d.device_plan()._delta is not None


@pytest.mark.parametrize("off, chunk", [(0, 4096), (0, 1), (37, 64),
                                        (1000, 512), (5000, 4096)])
def test_compact_range_matches_flatnonzero(off, chunk):
    """``compact_range`` returns hits ``off .. off + chunk - 1`` of each
    selected query in row order, ``-1`` past its count, and nothing for a
    selection row past the wave."""
    from repro.kernels.fused_scan import compact_range

    rng = np.random.default_rng(17)
    bits = rng.random((3, 2048 * 32)) < np.array([0.5, 0.02, 0.0])[:, None]
    bits[1, :40] = True                    # a run across word boundaries
    bits[2, -1] = True                     # the last bit of the last word
    words = (bits.reshape(3, 2048, 32).astype(np.uint64)
             << np.arange(32, dtype=np.uint64)).sum(-1)
    words = words.astype(np.uint32).view(np.int32)
    sel = np.array([2, 0, 1, 2**31 - 1], np.int32)
    out = np.asarray(jax.jit(compact_range, static_argnames=("chunk",))(
        words, sel, off, chunk=chunk))
    assert out.shape == (4, chunk)
    for row, q in enumerate(sel[:3]):
        want = np.flatnonzero(bits[q])[off:off + chunk]
        assert np.array_equal(out[row, :want.size], want)
        assert (out[row, want.size:] == -1).all()
    assert (out[3] == -1).all()


def test_large_pass_takes_several_chunks(monkeypatch):
    """Answers past ``LARGE_CHUNK`` take more compaction passes of one
    compiled shape, and still equal the numpy path."""
    from repro.engine import device

    monkeypatch.setattr(device, "LARGE_CHUNK", 256)
    ds = make_airline(6_000, seed=4)
    rects = rects_for(ds.data, n=10, seed=5)     # includes a full-range rect
    q_n, r_n = COAXIndex(ds.data).query_batch(rects)
    idx = COAXIndex(ds.data, backend="device", device_opts={"hit_cap": 16})
    q_d, r_d = idx.query_batch(rects)
    assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)
    assert np.bincount(q_n).max() > 4 * 256
    plan = idx.device_plan()
    # one scan shape and one compaction shape for each segment that overflowed
    assert plan.compile_count - plan.wave_compile_count <= 4
    stats = idx.device_stats()
    assert stats["large_dispatches"] > 2 * (plan.compile_count
                                            - plan.wave_compile_count)


def test_one_dispatch_per_wave_and_device_stats():
    """The §4 gate on CPU: every non-fallback wave is exactly ONE kernel
    dispatch (primary + outlier + delta fused), counted on the plan."""
    ds = make_osm(6_000, seed=8)
    idx = COAXIndex(ds.data, backend="device")
    rects = rects_for(ds.data, n=12, seed=9)
    ex = BatchQueryExecutor(idx, max_batch=4, backend="device")
    n_waves = -(-rects.shape[0] // 4)
    ex.execute(rects)
    s = ex.stats()
    assert s["device_fallbacks"] == 0 and s["fallback_waves"] == 0
    ds_stats = idx.device_stats()
    assert ds_stats is not None
    assert ds_stats["dispatches"] == s["waves"] == n_waves
    assert ds_stats["bytes_h2d"] > 0 and ds_stats["bytes_d2h"] > 0
    assert s["wave_p50_ms"] > 0 and s["wave_p99_ms"] >= s["wave_p50_ms"]
    # writes dirty the delta segment; still one dispatch per wave
    idx.insert(ds.data[:40] + 0.25)
    ex.execute(rects[:4])
    assert idx.device_stats()["dispatches"] == n_waves + 1


@pytest.mark.parametrize("route", [{}, {"use_pallas": True,
                                       "interpret": True}],
                         ids=["oracle", "pallas"])
def test_resident_drain_across_waves_with_interleaved_writes(route):
    """≥3 in-flight waves with inserts/deletes/compaction landing between
    submit and drain: every wave must answer from the snapshot+delta state
    it was SUBMITTED from (per-wave snapshot semantics), even across an
    epoch bump that swaps the grids out from under the in-flight tickets.
    A small ``hit_cap`` sends the larger answers through the drain-time
    large pass, which must scan the submit-time snapshot too."""
    rng = np.random.default_rng(31)
    ds = make_airline(6_000, seed=6)
    idx = COAXIndex(ds.data, backend="device",
                    device_opts={"hit_cap": 64, **route})
    rects = rects_for(ds.data, n=12, seed=11)     # under writes, too
    waves = [rects[0:4], rects[4:8], rects[8:12]]
    handles, expected = [], []
    e0 = idx.epoch
    for i, w in enumerate(waves):
        idx.backend = "numpy"
        expected.append(idx.query_batch(w))       # truth for CURRENT state
        idx.backend = "device"
        handles.append(idx.query_batch_submit(w))
        # writes land AFTER the submit, BEFORE any drain
        idx.insert(rng.normal(0, 5, (30, ds.data.shape[1])).astype(np.float32))
        idx.delete(np.arange(i * 7, i * 7 + 5))
        if i == 1:
            idx.compact()                         # epoch bump mid-stream
    assert idx.epoch > e0
    large = 0
    for (q_e, r_e), h in zip(expected, handles):
        q_d, r_d = idx.query_batch_collect(h)
        assert np.array_equal(q_d, q_e) and np.array_equal(r_d, r_e)
        assert idx.last_batch_stats.hit_overflows == 0
        over = int((np.bincount(q_e, minlength=4) > 64).sum())
        assert idx.last_batch_stats.large_results == over
        large += over
    assert large > 0
    # post-compaction wave: delta emptied then refilled; fresh plan epoch
    idx.backend = "numpy"
    q_e, r_e = idx.query_batch(rects[:6])
    idx.backend = "device"
    q_d, r_d = idx.query_batch(rects[:6])
    assert np.array_equal(q_d, q_e) and np.array_equal(r_d, r_e)


def test_server_pipelined_drain_device_equals_numpy():
    """QueryServer drain on the device backend (double-buffered submit one
    wave ahead of drain) with writes interleaving wave boundaries — same
    answers as a numpy server fed the identical admission sequence."""
    ds = make_airline(5_000, seed=12)
    rng = np.random.default_rng(41)
    rects = rects_for(ds.data, n=12, seed=13)
    extra = rng.normal(0, 5, (20, ds.data.shape[1])).astype(np.float32)

    def run(backend):
        srv = QueryServer(COAXIndex(ds.data), max_batch=4, backend=backend)
        qids = srv.submit_many(rects[:8])
        srv.insert(extra)
        qids += srv.submit_many(rects[8:])
        srv.delete(np.arange(10))
        res = srv.drain()
        return [res[q] for q in qids], srv

    got_d, srv_d = run("device")
    got_n, _ = run("numpy")
    for a, b in zip(got_d, got_n):
        assert np.array_equal(a, b)
    s = srv_d.stats()
    assert s["backend"] == "device" and s["waves_drained"] >= 3
    assert s["device_fallbacks"] == 0
    assert srv_d.executor.index.device_stats()["dispatches"] == s["waves"]


@pytest.mark.parametrize("plan", ["coax", "grid"])
def test_reanswer_span_on_hit_cap_overflow(plan):
    """A wave with a query over ``hit_cap`` records one ``device.large``
    span, after the wave's transfer, naming how many queries it answered,
    their hits, the slots it returned and the bytes it copied; then one
    ``device.assemble`` span.  No ``device.reanswer`` span is left."""
    from repro import obs

    ds = make_airline(6_000, seed=4)
    rects = rects_for(ds.data, n=10, seed=5)     # includes a full-range rect
    if plan == "coax":
        idx = COAXIndex(ds.data, backend="device",
                        device_opts={"hit_cap": 16})
        query = lambda: idx.query_batch(rects)
    else:
        idx = GridFile(ds.data, index_dims=[0, 1, 2], cells_per_dim=5,
                       backend="device", device_opts={"hit_cap": 16})
        query = lambda: idx.query_batch(rects[:, :3], rects)
    tr = obs.enable_tracing()
    try:
        q, _ = query()
    finally:
        obs.disable_tracing()
    st = idx.last_batch_stats
    sizes = np.bincount(q, minlength=rects.shape[0])
    assert st.hit_overflows == 0
    assert st.large_results == int((sizes > 16).sum()) > 0
    evs = tr.events()
    assert not [e for e in evs if e["name"] == "device.reanswer"]
    large = [e for e in evs if e["name"] == "device.large"]
    assert len(large) == 1
    args = large[0]["args"]
    assert args["queries"] == st.large_results
    # hits of a segment whose count fits the buffer come from the wave
    assert 0 < args["hits"] <= int(sizes[sizes > 16].sum())
    assert args["hit_budget"] >= args["hits"]
    assert args["bytes_d2h"] == 4 * args["hit_budget"]
    transfer = [e for e in evs if e["name"] == "device.transfer"]
    assert len(transfer) == 1 and transfer[0]["t1"] <= large[0]["t0"]
    assemble = [e for e in evs if e["name"] == "device.assemble"]
    assert len(assemble) == 1 and large[0]["t1"] <= assemble[0]["t0"]


# --------------------------------------------------------------------- #
# Work-list kernel (DESIGN.md §4): each query reads only its tiles
# --------------------------------------------------------------------- #
TILED_ROWS = 200_000          # padded to 2^18 rows: 8 kernel tiles


@pytest.fixture(scope="module")
def tiled_grid():
    """8 kernel tiles of a 16 x 16-cell grid on dims 0 and 1, sorted on
    dim 2: a box is one row run per dim-0 cell, runs ~12,500 rows apart."""
    rng = np.random.default_rng(51)
    data = rng.normal(0, 10, (TILED_ROWS, 3)).astype(np.float32)
    return GridFile(data, index_dims=[0, 1, 2], cells_per_dim=16, sort_dim=2)


def _near(gf, rng, b, half):
    """``b`` rects of half-widths ``half`` around rows of the grid."""
    c = gf.rows[rng.choice(gf.n_rows, b, replace=False)].astype(np.float64)
    return np.stack([c - half, c + half], axis=-1)


def _listed_wave(gf, case):
    """(rects, bucket, hit_cap, dead row ids) of one case."""
    rng = np.random.default_rng(1)
    several = _near(gf, rng, 4, np.array([2.0, 3.0, 5.0]))  # 7 of 8 items
    if case == "runs_and_tiles":
        return several, 4, 64, None
    if case == "empty_and_padding":
        empty = several[:1, :, ::-1].copy()         # lo > hi: an empty box
        point = gf.rows[:1, :, None].astype(np.float64) + [0.0, 0.0]
        return np.concatenate([empty, point, several[:1]]), 8, 64, None
    if case == "tombstones":
        dead = np.sort(rng.choice(gf.row_ids, TILED_ROWS // 3, replace=False))
        return several, 4, 64, dead
    if case == "past_hit_cap":
        return several, 4, 8, None
    assert case == "list_overflow"       # every query every tile: 32 > W
    return np.repeat(full_rect(3)[None], 4, axis=0), 4, 64, None


@pytest.mark.parametrize("case", ["runs_and_tiles", "empty_and_padding",
                                  "tombstones", "past_hit_cap",
                                  "list_overflow"])
def test_listed_kernel_matches_full_scan(tiled_grid, case):
    """The work-list kernel (interpret mode) against the full scan and a
    brute-force scan of the grid: counts, hit prefixes and ``scanned``
    equal, with the list at the plan's width ``W`` (the image's 8 tiles);
    a list past ``W`` takes the full-scan branch of the same program.  The
    large pass's uncompacted words (``fused_scan_words``) hold every hit,
    listed or not."""
    from repro.core.gridfile import f32_ceil
    from repro.engine.device import _GridImage
    from repro.kernels.fused_scan import fused_scan, fused_scan_words

    gf = tiled_grid
    rects, bp, cap, dead = _listed_wave(gf, case)
    img = _GridImage(gf)
    assert img.tiles == 8
    if dead is not None:
        img.set_alive(dead)
    first, last, n_cells = img.probe_batch(rects)
    seg, _, tiles = img.seg_inputs(rects, rects, first, last, bp,
                                   nw=img.tiles)
    b = rects.shape[0]
    assert tiles[1] == b * img.tiles
    assert tiles[2] == (case == "list_overflow")
    work = np.asarray(seg["work"])
    if case == "empty_and_padding":                  # no items for either
        assert n_cells[0] == 0 and 0 not in work[8:]
        assert 1 in work[8:] and 2 in work[8:]
    if case == "runs_and_tiles":
        assert (last[:, 0] - first[:, 0]).max() >= 1     # several runs
        per_query = np.bincount(work[8:][work[8:] >= 0], minlength=b)
        assert per_query.max() >= 2 and tiles[0] < tiles[1]

    ops = [seg[k] for k in ("rows", "flo", "fhi", "alive", "coords", "first",
                            "last", "sv", "tband")]
    listed = fused_scan(*ops, work=seg["work"], hit_cap=cap, interpret=True)
    full = fused_scan(*ops, hit_cap=cap, interpret=True)
    for x, y in zip(listed, full):
        assert np.array_equal(np.asarray(x), np.asarray(y))

    # brute force over the grid's own cell-major rows
    alive = np.ones(gf.n_rows, bool)
    if dead is not None:
        alive = ~np.isin(gf.row_ids, dead)
    rows = gf.rows.astype(np.float64)
    cell = np.repeat(np.arange(gf.n_cells), np.diff(gf.offsets))
    coords = np.stack([cell // 16, cell % 16], axis=1)
    sv = gf.sort_vals
    counts, hits, scanned = (np.asarray(x) for x in listed)
    words = np.asarray(fused_scan_words(*ops, work=seg["work"],
                                        interpret=True))
    assert np.array_equal(words, np.asarray(
        fused_scan_words(*ops, interpret=True)))
    bits = np.unpackbits(words.view(np.uint8), axis=1, bitorder="little")
    for q in range(bp):
        if q >= b:                                      # padding: inert
            assert counts[q, 0] == scanned[q, 0] == 0
            assert not bits[q].any()
            continue
        hit = alive & np.all((rows >= rects[q, :, 0])
                             & (rows < rects[q, :, 1]), axis=1)
        cand = (alive & np.all((coords >= first[q]) & (coords <= last[q]),
                               axis=1)
                & (sv >= f32_ceil(rects[q, 2, 0]))
                & (sv < f32_ceil(rects[q, 2, 1])))
        want = np.nonzero(hit)[0]
        assert np.array_equal(np.nonzero(bits[q])[0], want)
        assert counts[q, 0] == want.size
        assert scanned[q, 0] == cand.sum()
        take = min(want.size, cap)
        assert np.array_equal(hits[q, :take], want[:take])
        assert (hits[q, take:] == -1).all()
    if case == "past_hit_cap":
        assert counts[:b, 0].max() > cap


def test_listed_waves_share_one_shape_per_bucket():
    """COAX waves whose lists read different numbers of tiles, and a wave
    whose list does not fit (the full-scan branch), all run one compiled
    program per bucket, answer as numpy does, and count their tiles on
    the ``device.inputs`` span and in the registry."""
    from repro import obs
    from repro.data import knn_rect_queries

    ds = make_airline(400_000, seed=3)
    idx = COAXIndex(ds.data, device_opts={"use_pallas": True,
                                          "interpret": True})
    waves = [knn_rect_queries(ds.data, 4, k, seed=k) for k in (10, 100)]
    waves.append(np.repeat(full_rect(ds.data.shape[1])[None], 3, axis=0))
    full = obs.get_registry().counter("coax_device_fullscan_segments_total")
    full0 = full.total()
    tr = obs.enable_tracing()
    try:
        for rects in waves:
            idx.backend = "numpy"
            q_n, r_n = idx.query_batch(rects)
            idx.backend = "device"
            q_d, r_d = idx.query_batch(rects)
            assert np.array_equal(q_d, q_n) and np.array_equal(r_d, r_n)
    finally:
        obs.disable_tracing()
    plan = idx.device_plan()
    assert plan._nw == plan.p_img.tiles == 16
    assert plan.wave_compile_count == 1
    # the full-range wave's answers come from the large pass, whose scan
    # takes the full-scan branch of the same list
    assert idx.last_batch_stats.large_results == 3
    args = [e["args"] for e in tr.events() if e["name"] == "device.inputs"]
    listed = [a["tiles_listed"] for a in args]
    image = [a["tiles_image"] for a in args]
    assert len(set(listed[:2])) == 2 and listed[0] < image[0]
    assert listed[2] == image[2]              # the full-scan branch
    assert full.total() - full0 >= 1
