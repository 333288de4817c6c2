"""Fig. 6: point + range query runtime, COAX vs R-Tree / uniform grid /
column files / full scan, on airline-like and OSM-like data.

Per the paper's methodology (§8.2.1: 'We use the configuration that performs
best for each index'), every engine's resolution knob is tuned on a held-out
query subset before measurement.

``--batch`` switches to the throughput mode (DESIGN.md §2): QPS of the
batched engine (``COAXIndex.query_batch`` through ``BatchQueryExecutor``)
vs the per-query loop across batch sizes, emitted to ``BENCH_queries.json``.
``--backend {numpy,device,both}`` additionally sweeps the device-resident
serving plane (DESIGN.md §4) over the same waves — the ``device_qps``
section — asserting both backends return identical hits before timing.
``--mixed`` drives the mutable lifecycle (DESIGN.md §5): a ``QueryServer``
interleaving query waves with insert/delete admissions at a sweep of write
ratios (FD-violating insert bursts included, so compaction and drift
relearns fire), emitted to ``BENCH_updates.json``.
``--shards K[,K...]`` sweeps the scatter-gather plane (DESIGN.md §6): a
``ShardedCOAX`` per shard count, range-partitioned, served through the
executor's sharded mode — per-K QPS, pruning rate and per-shard work merge
into the ``sharded`` section of ``BENCH_queries.json``.
``--recover`` drives the durability plane (DESIGN.md §7): snapshot size
and save latency, then recovery time as a function of WAL length (the
replay tail), emitted to ``BENCH_storage.json``.
``--cache`` drives the semantic result cache (DESIGN.md §9): a Zipfian
hot-rect sweep (cached vs uncached QPS, bit-identity gated) plus the
pinned-epoch MVCC drill, emitted to the ``cache`` section of
``BENCH_queries.json``.  Every mode owns ONE top-level section of its
BENCH file and merge-preserves the others.
``--smoke`` shrinks the sweep and turns the throughput/agreement checks
into hard assertions for CI — for ``--mixed`` the gate is hit agreement
between the mutated index and a rebuild-from-scratch oracle, for
``--shards`` it is cross-shard vs single-index hit agreement, for
``--recover`` it is recovered-vs-live hit agreement at every WAL length.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from .common import PCFG, dataset, emit, queries, time_queries
from repro.compile_cache import enable_compile_cache
from repro.core import (COAXIndex, CoaxConfig, ColumnFiles, FullScan, STRTree,
                        UniformGrid, point_rect)
from repro.data import knn_rect_queries
from repro.engine import BatchQueryExecutor

SWEEPS = {
    "coax": [8, 16, 32, 64],
    "uniform_grid": [3, 4, 6, 8, 12],
    "column_files": [3, 4, 6, 8, 12],
    "r_tree": [6, 10, 16],
}


def _read_bench_json(path: Path) -> dict:
    """Existing benchmark doc at ``path``, or {} (missing/corrupt) — so
    every mode can preserve the other modes' sections."""
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def _write_bench_section(out_path, default_name: str, section: str,
                         result: dict) -> Path:
    """Merge ``result`` under the ``section`` key of a shared BENCH file,
    preserving EVERY foreign top-level key.

    All writers of a shared file go through here: each mode owns exactly
    one top-level section and never sees the others.  (run_batch used to
    hand-preserve only "sharded" and run_mixed overwrote BENCH_updates.json
    wholesale, so any other section — including the cache sweep — was
    silently clobbered by a re-run of a sibling mode.)
    """
    out = Path(out_path) if out_path else \
        Path(__file__).resolve().parents[1] / default_name
    merged = _read_bench_json(out)
    merged[section] = result
    out.write_text(json.dumps(merged, indent=2) + "\n")
    return out


def _build(name, data, knob):
    if name == "coax":
        return COAXIndex(data, CoaxConfig(primary_cells_per_dim=knob))
    if name == "uniform_grid":
        return UniformGrid(data, cells_per_dim=knob)
    if name == "column_files":
        return ColumnFiles(data, cells_per_dim=knob)
    if name == "r_tree":
        return STRTree(data, leaf_cap=knob, node_cap=knob)
    return FullScan(data)


def tuned_engine(name, data, tune_rects):
    """Pick the best-latency knob on the tuning subset (paper §8.2.1)."""
    if name == "full_scan":
        return FullScan(data), None
    best = None
    for knob in SWEEPS[name]:
        eng = _build(name, data, knob)
        us, _ = time_queries(eng, tune_rects)
        if best is None or us < best[1]:
            best = (eng, us, knob)
    return best[0], best[2]


def run(rows: int = None, n_queries: int = None) -> dict:
    rows = rows or PCFG.airline_rows
    n_q = n_queries or PCFG.n_queries
    out = {}
    for ds_name, ds_rows in (("airline", rows), ("osm", rows)):
        ds = dataset(ds_name, ds_rows)
        rects = queries(ds_name, ds_rows, n_q, PCFG.knn_k)
        tune = rects[: max(8, n_q // 8)]
        measure = rects[max(8, n_q // 8):]
        rng = np.random.default_rng(PCFG.seed)
        pts = ds.data[rng.choice(ds.data.shape[0], n_q, replace=False)]
        point_rects = np.stack([point_rect(p) for p in pts])

        for name in ("coax", "uniform_grid", "column_files", "r_tree", "full_scan"):
            eng, knob = tuned_engine(name, ds.data, tune)
            us_r, n_res = time_queries(eng, measure)
            us_p, _ = time_queries(eng, point_rects)
            out[(ds_name, name)] = {"range_us": us_r, "point_us": us_p,
                                    "knob": knob, "results": int(n_res)}
            emit(f"fig6/{ds_name}/{name}/range", us_r, f"results={n_res},knob={knob}")
            emit(f"fig6/{ds_name}/{name}/point", us_p, f"knob={knob}")

        best_rival = min(out[(ds_name, n)]["range_us"] for n in
                         ("uniform_grid", "column_files", "r_tree"))
        speedup = best_rival / out[(ds_name, "coax")]["range_us"]
        emit(f"fig6/{ds_name}/coax_speedup_vs_best_rival", speedup,
             "x faster (paper: ~1.25x)")
    return out


def run_batch(rows: int = 100_000, n_queries: int = 256,
              batch_sizes=(1, 8, 16, 64, 256),
              out_path: str = None, backend: str = "both",
              smoke: bool = False) -> dict:
    """Throughput mode: QPS vs wave width, batched engine vs per-query loop.

    Both paths answer the same rects on the same index; per-wave results are
    checked for set equality against the loop before timing is reported.
    ``backend`` sweeps the numpy path, the device-resident plan (DESIGN.md
    §4), or both.  Each sweep point also records p50/p99 wave latency
    (submit→drain, so the device pipeline's overlap shows up in QPS but not
    in per-wave latency) and the device sweep records the plan's rollups
    (compile cache size, kernel dispatches, transfer bytes both ways).

    ``smoke`` turns the sweep into the CI gate: batch QPS beats the
    per-query loop, all backends agree on hit counts, every non-fallback
    device wave is exactly ONE fused kernel dispatch, and — on a real
    accelerator only — ``device_speedup > 1`` at batch ≥ 64 (CPU interpret
    mode is a correctness harness, not a fast path, so the speedup gate is
    skipped there).
    """
    if smoke:
        batch_sizes = tuple(bs for bs in batch_sizes if bs <= 64) or (1, 64)
    ds = dataset("airline", rows)
    rects = np.asarray(queries("airline", rows, n_queries, PCFG.knn_k))
    idx = COAXIndex(ds.data)

    # per-query loop baseline (the seed's only path)
    t0 = time.perf_counter()
    loop_hits = [idx.query(r) for r in rects]
    single_s = time.perf_counter() - t0
    single_qps = len(rects) / single_s
    emit("batch/airline/per_query_loop_qps", single_qps,
         f"rows={rows},queries={len(rects)}")

    result = {
        "dataset": "airline", "rows": rows, "n_queries": len(rects),
        "single_qps": single_qps, "batch_qps": {}, "speedup": {},
        "wave_latency_ms": {},
    }
    backends = ("numpy", "device") if backend == "both" else (backend,)
    hit_counts = {}
    for bk in backends:
        if bk == "device":
            result["device_qps"] = {}
            result["device_speedup"] = {}
        qps_key = "batch_qps" if bk == "numpy" else "device_qps"
        spd_key = "speedup" if bk == "numpy" else "device_speedup"
        result["wave_latency_ms"][bk] = {}
        for bs in batch_sizes:
            ex = BatchQueryExecutor(idx, max_batch=bs, backend=bk)
            got = ex.execute(rects)      # warm + compile + correctness pass
            assert all(np.array_equal(g, w)
                       for g, w in zip(got, loop_hits)), (bk, bs)
            ex.reset_stats()
            dev0 = idx.device_stats() if bk == "device" else None
            t0 = time.perf_counter()
            ex.execute(rects)
            dt = time.perf_counter() - t0
            qps = len(rects) / dt
            result[qps_key][bs] = qps
            result[spd_key][bs] = qps / single_qps
            s = ex.stats()
            hit_counts[(bk, bs)] = s["hits"]
            result["wave_latency_ms"][bk][bs] = {
                "p50": s["wave_p50_ms"], "p99": s["wave_p99_ms"]}
            if bk == "device" and dev0 is not None:
                # §4 gate: one fused kernel launch per non-fallback wave
                disp = idx.device_stats()["dispatches"] - dev0["dispatches"]
                assert disp == s["waves"] - s["fallback_waves"], (
                    f"{disp} dispatches for {s['waves']} waves "
                    f"({s['fallback_waves']} fallbacks) at batch={bs}")
            emit(f"batch/airline/{bk}_qps@{bs}", qps,
                 f"speedup={qps / single_qps:.2f}x,"
                 f"p50={s['wave_p50_ms']:.2f}ms,p99={s['wave_p99_ms']:.2f}ms,"
                 f"rows_scanned={s['rows_scanned']},"
                 f"cells_probed={s['cells_probed']},"
                 f"fallbacks={s['device_fallbacks']},"
                 f"hit_overflows={s['hit_overflows']}")
        if bk == "device":
            dstats = idx.device_stats()
            result["device_stats"] = dstats      # compile_count + transfers
            emit("batch/airline/device_plan", float(dstats["dispatches"]),
                 f"compile_count={dstats['compile_count']},"
                 f"bytes_h2d={dstats['bytes_h2d']},"
                 f"bytes_d2h={dstats['bytes_d2h']}")
    idx.backend = "numpy"

    if smoke:
        # the throughput gate is numpy-batch vs per-query loop; a device-only
        # sweep on CPU legitimately trails the loop (the device plane targets
        # real accelerators), so only gate when the numpy sweep ran
        if result["batch_qps"]:
            best_batch = max(result["batch_qps"].values())
            assert best_batch >= single_qps, (
                f"batch path regressed: {best_batch:.0f} qps < per-query "
                f"loop {single_qps:.0f} qps")
        assert hit_counts, "smoke ran no backend sweep"
        counts = set(hit_counts.values())
        assert len(counts) == 1, f"backends disagree on hit counts: {hit_counts}"
        if result.get("device_speedup"):
            import jax
            if jax.default_backend() != "cpu":   # real accelerator only
                best_dev = max(v for b, v in result["device_speedup"].items()
                               if b >= 64)
                assert best_dev > 1.0, (
                    f"device plane slower than per-query loop on "
                    f"{jax.default_backend()}: {best_dev:.2f}x at batch>=64")
        emit("batch/airline/smoke", 1.0,
             f"batch>=single ok, hit counts agree ({counts.pop()}), "
             f"one dispatch per device wave")

    _write_bench_section(out_path, "BENCH_queries.json", "batch", result)
    print(f"BENCH {json.dumps(result)}")
    return result


def run_sharded(rows: int = 100_000, n_queries: int = 256,
                shard_counts=(1, 2, 4, 8), batch: int = 64,
                partition: str = "range", out_path: str = None,
                backend: str = "numpy", smoke: bool = False) -> dict:
    """Scatter-gather scaling mode (DESIGN.md §6).

    For each shard count K an airline-rows ``ShardedCOAX`` (range partition
    on the distance attribute, each shard learning its own FDs) answers the
    same rect set through the executor's sharded mode, on ``backend``
    (``"numpy"`` or ``"device"`` — per-shard ``DevicePlan``s; recorded in
    the output).  Reported per K: sustained QPS vs the single-index
    baseline on the same backend, the shard-pruning rate (fraction of
    (query, shard) pairs the bbox test skipped) and the per-shard work
    rollup.  Every K's hits are asserted bit-identical to the single index
    before timing; ``smoke`` keeps that gate as the CI assertion and
    shrinks nothing else (the sweep is already small).  Results merge into
    the ``sharded`` key of ``BENCH_queries.json`` so the batch-mode
    sections survive.
    """
    from repro.engine import ShardedCOAX

    if backend not in ("numpy", "device"):
        raise ValueError(f"--shards sweeps one backend at a time, got {backend!r}")
    ds = dataset("airline", rows)
    rects = np.asarray(queries("airline", rows, n_queries, PCFG.knn_k))
    single = COAXIndex(ds.data, backend=backend)
    ex1 = BatchQueryExecutor(single, max_batch=batch)
    base_hits = ex1.execute(rects)               # warm + correctness anchor
    ex1.reset_stats()
    t0 = time.perf_counter()
    ex1.execute(rects)
    single_qps = len(rects) / (time.perf_counter() - t0)
    emit("sharded/airline/single_index_qps", single_qps,
         f"rows={rows},queries={len(rects)},batch={batch},backend={backend}")

    result = {"dataset": "airline", "rows": rows, "n_queries": len(rects),
              "batch": batch, "partition": partition, "backend": backend,
              "single_qps": single_qps, "shards": {}}
    for k in shard_counts:
        idx = ShardedCOAX(ds.data, n_shards=k, partition=partition,
                          backend=backend)
        ex = BatchQueryExecutor(idx, max_batch=batch, shards=k)
        got = ex.execute(rects)                  # warm + agreement gate
        assert all(np.array_equal(g, w) for g, w in zip(got, base_hits)), \
            f"sharded hits disagree with single index at K={k}"
        ex.reset_stats()
        t0 = time.perf_counter()
        ex.execute(rects)
        dt = time.perf_counter() - t0
        qps = len(rects) / dt
        s = ex.stats()
        scattered = sum(p["queries"] for p in s["per_shard"])
        pruned = 1.0 - scattered / (len(rects) * k)
        result["shards"][str(k)] = {
            "qps": qps, "speedup_vs_single": qps / single_qps,
            "pruned_frac": pruned, "rows_scanned": s["rows_scanned"],
            "per_shard": s["per_shard"], "shard_sizes": idx.shard_sizes(),
        }
        emit(f"sharded/airline/qps@K{k}", qps,
             f"speedup={qps / single_qps:.2f}x,pruned={pruned:.2f},"
             f"rows_scanned={s['rows_scanned']}")
    if smoke:
        emit("sharded/airline/smoke", 1.0,
             f"hit agreement ok across K={list(shard_counts)} "
             f"({len(rects)} rects)")

    _write_bench_section(out_path, "BENCH_queries.json", "sharded", result)
    print(f"BENCH {json.dumps(result)}")
    return result


def run_mixed(rows: int = 50_000, n_queries: int = 192,
              insert_ratios=(0.1, 0.25, 0.5, 0.75), batch: int = 64,
              out_path: str = None, smoke: bool = False) -> dict:
    """Mixed read/write workload (DESIGN.md §5).

    For each write ratio ``r`` a fresh ``COAXIndex`` with BACKGROUND
    compaction (§5.4) is driven through a ``QueryServer``: every wave of
    ``batch`` queries is preceded by ``r/(1-r)`` write admissions —
    inserts of 32-row batches drawn from held-out airline rows (every 4th
    batch FD-VIOLATING, so the outlier delta and the drift tracker see
    real work) and deletes of 16 random original ids — flushed at the wave
    boundary under the server's per-wave snapshot semantics.  Reported per
    ratio: sustained query QPS, write throughput, the lifecycle counters
    (epoch, compactions, residual delta rows), and the SERVING-PAUSE
    profile — median / p99 / max gap between wave completions, the metric
    a synchronous stop-the-world compaction blows up and an epoch handoff
    must not.  A read-only baseline (``read_only`` key) anchors the
    "writes must not halve reads" comparison.  ``smoke`` gates every
    ratio's final state on hit agreement with a rebuild-from-scratch
    oracle (a fresh ``COAXIndex`` over ``live_rows()``), on the device
    backend too when jax is present, and gates the pause profile at
    r=0.5: no wave gap may exceed 5x the median wave latency.
    """
    from repro.engine import QueryServer

    ds = dataset("airline", rows * 2)           # second half = insert pool
    base = np.ascontiguousarray(ds.data[:rows])
    pool = ds.data[rows:].copy()
    dep_col = 1                                 # airline FD: distance -> elapsed
    rects = knn_rect_queries(base, n_queries, PCFG.knn_k,
                             seed=PCFG.seed, sample_cap=100_000)
    result = {"dataset": "airline", "rows": rows, "n_queries": int(n_queries),
              "batch": batch, "insert_rows_per_op": 32, "ratios": {}}

    def _drive(idx, ratio):
        """One sweep of the query waves at write ratio ``ratio``; returns
        the server, elapsed seconds and per-wave completion gaps."""
        srv = QueryServer(idx, max_batch=batch)
        rng = np.random.default_rng(PCFG.seed + int(ratio * 1000))
        pool_pos, n_ins_batches = 0, 0
        writes_per_wave = ratio / max(1.0 - ratio, 1e-9)
        owed = 0.0
        t0 = time.perf_counter()
        done = []
        for start in range(0, len(rects), batch):
            wave = rects[start:start + batch]
            owed += writes_per_wave * len(wave)
            while owed >= 1.0:
                owed -= 1.0
                if n_ins_batches % 3 == 2:      # 1 delete per 2 inserts
                    srv.delete(rng.integers(0, rows, 16))
                else:
                    rows_in = pool[pool_pos:pool_pos + 32].copy()
                    pool_pos = (pool_pos + 32) % max(len(pool) - 32, 1)
                    if n_ins_batches % 8 == 6:  # FD-violating burst
                        rows_in[:, dep_col] = rows_in[:, dep_col] * 3.0 + 500.0
                    srv.insert(rows_in)
                n_ins_batches += 1
            for r in wave:
                srv.submit(r)
            srv.drain()
            done.append(time.perf_counter())
        gaps = np.diff(np.asarray([t0] + done))
        return srv, done[-1] - t0, gaps

    _drive(COAXIndex(base), 0.0)                # warmup (first drive in a
    _, ro_dt, _ = _drive(COAXIndex(base), 0.0)  # process runs several x cold)
    ro_qps = len(rects) / ro_dt
    result["read_only"] = {"qps": ro_qps}
    emit("mixed/airline/qps@read_only", ro_qps, "no write admissions")

    for ratio in insert_ratios:
        idx = COAXIndex(base, CoaxConfig(background_compact=True))
        srv, dt, gaps = _drive(idx, ratio)
        idx.finish_handoff()                    # join any in-flight build
        s = srv.stats()
        entry = {
            "qps": len(rects) / dt,
            "writes_per_s": s["writes_applied"] / dt,
            "rows_inserted": s["rows_inserted"],
            "rows_deleted": s["rows_deleted"],
            "epoch": s["epoch"],
            "compactions": s["compactions"],
            "background_compactions": idx.background_compactions,
            "final_delta_rows": s["delta_rows"],
            "final_tombstones": s["tombstones"],
            "wave_median_ms": float(np.median(gaps) * 1e3),
            "pause_p99_ms": float(np.percentile(gaps, 99) * 1e3),
            "pause_max_ms": float(np.max(gaps) * 1e3),
        }
        result["ratios"][str(ratio)] = entry
        emit(f"mixed/airline/qps@r{ratio}", entry["qps"],
             f"writes/s={entry['writes_per_s']:.1f},"
             f"inserted={entry['rows_inserted']},deleted={entry['rows_deleted']},"
             f"epoch={entry['epoch']},compactions={entry['compactions']},"
             f"pause_max={entry['pause_max_ms']:.1f}ms,"
             f"wave_median={entry['wave_median_ms']:.1f}ms")

        if smoke:
            if ratio == 0.5:
                # the serving-pause gate: a stop-the-world compaction shows
                # up as one wave gap many multiples of the median; the §5.4
                # handoff keeps the profile flat
                assert entry["pause_max_ms"] <= 5 * entry["wave_median_ms"], \
                    (f"serving pause {entry['pause_max_ms']:.1f}ms exceeds "
                     f"5x median wave {entry['wave_median_ms']:.1f}ms")
                emit("mixed/airline/pause@r0.5", entry["pause_max_ms"],
                     f"<= 5x median ({entry['wave_median_ms']:.1f}ms) ok")
            # rebuild-from-scratch oracle: a fresh index over the final live
            # row set must agree bit-for-bit with the mutated index
            live, ids = idx.live_rows()
            oracle = COAXIndex(live, row_ids=ids)
            got = idx.query_batch_split(np.asarray(rects))
            want = oracle.query_batch_split(np.asarray(rects))
            assert all(np.array_equal(g, w) for g, w in zip(got, want)), \
                f"mixed-wave hits disagree with scratch oracle at r={ratio}"
            idx.backend = "device"
            got_d = idx.query_batch_split(np.asarray(rects))
            idx.backend = "numpy"
            assert all(np.array_equal(g, w) for g, w in zip(got_d, want)), \
                f"device mixed-wave hits disagree with oracle at r={ratio}"
            assert s["writes_applied"] > 0 and s["rows_inserted"] > 0
            emit(f"mixed/airline/smoke@r{ratio}", 1.0,
                 f"oracle agreement ok ({len(rects)} rects)")

    _write_bench_section(out_path, "BENCH_updates.json", "mixed", result)
    print(f"BENCH {json.dumps(result)}")
    return result


def run_recover(rows: int = 100_000, n_queries: int = 128,
                wal_lengths=(0, 64, 256, 1024), out_path: str = None,
                smoke: bool = False) -> dict:
    """Durability mode (DESIGN.md §7): cost of the crash-safety plane.

    One airline-rows ``COAXIndex`` is journaled into a scratch directory;
    reported: full-state snapshot bytes vs raw data bytes, (atomic) save
    latency, cold restore latency at WAL length 0, then — for each WAL
    length W — the recovery time of a crash after W journaled write ops
    (every 4th op a delete, every 8th an FD-violating insert burst, 32 rows
    per insert) and the replayed-record count.  Every recovery is gated on
    flat-hit agreement with the never-crashed index (``smoke`` keeps the
    gate and shrinks the sweep).  Results land in the ``recover`` section
    of ``BENCH_storage.json``; other sections are merge-preserved.
    """
    import shutil
    import tempfile

    from repro.storage import read_manifest, restore, snapshot_nbytes

    if smoke:
        wal_lengths = tuple(w for w in wal_lengths if w <= 256) or (0, 64)
    ds = dataset("airline", rows * 2)            # second half = insert pool
    base = np.ascontiguousarray(ds.data[:rows])
    pool = ds.data[rows:].copy()
    rects = np.asarray(queries("airline", rows, n_queries, PCFG.knn_k))
    result = {"dataset": "airline", "rows": rows, "n_queries": len(rects),
              "data_bytes": int(base.nbytes), "wal": {}}

    idx = COAXIndex(base, CoaxConfig(auto_compact=False))
    live_hits = idx.query_batch_split(rects)
    workdir = Path(tempfile.mkdtemp(prefix="bench_recover_"))
    try:
        t0 = time.perf_counter()
        snap = idx.save(workdir / "cold")
        result["save_s"] = time.perf_counter() - t0
        result["snapshot_bytes"] = snapshot_nbytes(snap)
        emit("recover/airline/save_s", result["save_s"],
             f"snapshot={result['snapshot_bytes']}B,"
             f"data={result['data_bytes']}B")
        t0 = time.perf_counter()
        cold = restore(workdir / "cold")
        result["restore_cold_s"] = time.perf_counter() - t0
        emit("recover/airline/restore_cold_s", result["restore_cold_s"],
             "warm restart, zero-length WAL")
        assert all(np.array_equal(g, w) for g, w in
                   zip(cold.query_batch_split(rects), live_hits)), \
            "cold restore disagrees with live index"

        for w in wal_lengths:
            d = workdir / f"wal_{w}"
            vic = COAXIndex(base, CoaxConfig(auto_compact=False))
            vic.attach_durability(d)
            rng = np.random.default_rng(PCFG.seed)
            pos = 0
            for op in range(w):
                if op % 4 == 3:
                    vic.delete(rng.integers(0, rows, 16))
                else:
                    rows_in = pool[pos:pos + 32].copy()
                    pos = (pos + 32) % max(len(pool) - 32, 1)
                    if op % 8 == 6:
                        rows_in[:, 1] = rows_in[:, 1] * 3.0 + 500.0
                    vic.insert(rows_in)
            vic.durable.sync()
            want = vic.query_batch_split(rects)
            wal_bytes = vic.durable.describe()["wal_bytes"]
            del vic                               # crash
            t0 = time.perf_counter()
            rec = restore(d, durable=True)
            dt = time.perf_counter() - t0
            assert all(np.array_equal(g, x) for g, x in
                       zip(rec.query_batch_split(rects), want)), \
                f"recovery disagrees with live index at WAL length {w}"
            result["wal"][str(w)] = {
                "recovery_s": dt, "wal_bytes": int(wal_bytes),
                "replayed": int(rec.durable.wal.next_seq),
            }
            emit(f"recover/airline/recovery_s@wal{w}", dt,
                 f"wal_bytes={wal_bytes},agreement=ok")
        if smoke:
            emit("recover/airline/smoke", 1.0,
                 f"recovered==live at WAL lengths {list(wal_lengths)} "
                 f"({len(rects)} rects)")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    _write_bench_section(out_path, "BENCH_storage.json", "recover", result)
    print(f"BENCH {json.dumps(result)}")
    return result


def run_failover(rows: int = 50_000, n_queries: int = 96, n_ops: int = 48,
                 out_path: str = None, smoke: bool = False) -> dict:
    """Replication mode (DESIGN.md §8): cost + correctness of failover.

    A 2-replica ``ReplicatedServer`` over airline rows streams an
    insert/delete schedule while a scripted ``FaultPlan`` damages the wire
    (drops, torn frames, duplicates, reordering, transport errors — all
    repaired through catch-up).  Reported: shipping overhead on the write
    path, frames/bytes shipped, replica convergence (lag drained to 0),
    then two failover drills — the primary killed MID-STREAM with an
    acked-but-unpumped tail, and killed MID-COMPACTION-ROTATION (the §7.5
    crash window) — each measuring promotion latency and gating the
    promoted frontier ≥ the last acked write.  Every stage asserts
    bit-identical flat hits against a never-crashed oracle index replaying
    the same ops.  Results land in the ``failover`` section of
    ``BENCH_storage.json``; other sections are merge-preserved.
    """
    import shutil
    import tempfile

    from repro.replication import ReplicatedServer
    from repro.runtime.failure import FaultPlan

    if smoke:
        n_ops = min(n_ops, 24)
    ds = dataset("airline", rows * 2)            # second half = insert pool
    base = np.ascontiguousarray(ds.data[:rows])
    pool = ds.data[rows:].copy()
    rects = np.asarray(queries("airline", rows, n_queries, PCFG.knn_k))
    result = {"dataset": "airline", "rows": rows, "n_queries": len(rects),
              "n_ops": n_ops}

    def op_stream(target, upto):
        rng = np.random.default_rng(PCFG.seed)
        pos = 0
        for op in range(upto):
            if op % 4 == 3:
                target.delete(rng.integers(0, rows, 16))
            else:
                rows_in = pool[pos:pos + 48].copy()
                pos += 48
                if op % 8 == 6:
                    rows_in[:, 1] = rows_in[:, 1] * 3.0 + 500.0
                target.insert(rows_in)
            yield op

    def flat(index):
        return index.query_batch_split(rects)

    def agree(a, b):
        return all(np.array_equal(x, y) for x, y in zip(a, b))

    workdir = Path(tempfile.mkdtemp(prefix="bench_failover_"))
    try:
        # ---------------- drill 1: faulty wire + mid-stream kill -------- #
        plan = FaultPlan({
            "ship.replica-0": {3: "drop", 7: "tear", 11: "dup",
                               15: ("tear", 9), 19: "drop"},
            "ship.replica-1": {5: "reorder", 9: ("error", 2),
                               13: ("delay", 2), 17: "tear"},
        })
        oracle = COAXIndex(base.copy(), CoaxConfig(auto_compact=False))
        srv = ReplicatedServer(
            COAXIndex(base, CoaxConfig(auto_compact=False)), workdir / "d1",
            n_replicas=2, plan=plan)
        t0 = time.perf_counter()
        for op in op_stream(srv, n_ops):
            if op % 3 == 2:
                srv.tick()
        write_s = time.perf_counter() - t0
        for _ in op_stream(oracle, n_ops):
            pass
        srv.compact()                            # ships the ROTATE frame
        oracle.compact()
        t0 = time.perf_counter()
        for _ in range(16):
            srv.tick()
            if all(r.lag_frames() == 0 for r in srv.replicas):
                break
        converge_s = time.perf_counter() - t0
        st = srv.stats()
        assert all(r["lag_frames"] == 0 for r in st["replicas"]), \
            "replicas failed to drain their lag"
        want = flat(oracle)
        for rep in srv.replicas:
            assert agree(flat(rep.index), want), \
                f"{rep.name} diverged from the never-crashed oracle"
        result["ship"] = {
            "write_path_s": write_s, "converge_s": converge_s,
            "frames": st["ship"]["shipped_frames"],
            "bytes": st["ship"]["shipped_bytes"],
            "send_retries": st["ship"]["send_retries"],
            "transport_faults": st["transport_faults"],
        }
        emit("failover/airline/ship_frames", st["ship"]["shipped_frames"],
             f"bytes={st['ship']['shipped_bytes']},"
             f"faults={sum(st['transport_faults'].values())},agreement=ok")

        # primary dies with an acked tail the replicas never saw shipped
        srv.insert(pool[-64:])
        oracle.insert(pool[-64:])
        acked = srv.acked
        srv.kill_primary()
        t0 = time.perf_counter()
        promoted = srv.promote()
        promote_s = time.perf_counter() - t0
        assert promoted.frontier >= acked, \
            f"promotion lost acked writes: {promoted.frontier} < {acked}"
        assert agree(flat(promoted.index), flat(oracle)), \
            "promoted index diverged from the never-crashed oracle"
        for op in op_stream(srv, 4):             # writes resume post-promotion
            srv.tick()
        for _ in op_stream(oracle, 4):
            pass
        for _ in range(8):
            srv.tick()
        assert agree(flat(srv.primary), flat(oracle)), \
            "post-promotion writes diverged from the oracle"
        result["promote_midstream_s"] = promote_s
        emit("failover/airline/promote_midstream_s", promote_s,
             f"frontier={promoted.frontier}>=acked={acked},agreement=ok")

        # ---------------- drill 2: kill mid-compaction-rotation --------- #
        plan2 = FaultPlan({"primary.rotate": {0: "crash"}})
        oracle2 = COAXIndex(base.copy(), CoaxConfig(auto_compact=False))
        srv2 = ReplicatedServer(
            COAXIndex(base, CoaxConfig(auto_compact=False)), workdir / "d2",
            n_replicas=2, plan=plan2)
        for op in op_stream(srv2, n_ops // 2):
            if op % 3 == 2:
                srv2.tick()
        for _ in op_stream(oracle2, n_ops // 2):
            pass
        acked2 = srv2.acked
        try:
            srv2.compact()                       # dies inside the §7.5 window
            raise AssertionError("rotation crash did not fire")
        except RuntimeError:
            pass
        oracle2.compact()                        # ...but the rotation is on disk
        srv2.kill_primary()
        t0 = time.perf_counter()
        promoted2 = srv2.promote()
        promote2_s = time.perf_counter() - t0
        assert promoted2.frontier >= acked2
        assert promoted2.index.epoch == oracle2.epoch
        assert agree(flat(promoted2.index), flat(oracle2)), \
            "mid-rotation promotion diverged from the never-crashed oracle"
        result["promote_midrotation_s"] = promote2_s
        emit("failover/airline/promote_midrotation_s", promote2_s,
             f"epoch={promoted2.index.epoch},agreement=ok")
        if smoke:
            emit("failover/airline/smoke", 1.0,
                 f"bit-identity held over {n_ops} ops, 2 kills, "
                 f"{sum(st['transport_faults'].values())} wire faults "
                 f"({len(rects)} rects)")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    _write_bench_section(out_path, "BENCH_storage.json", "failover", result)
    print(f"BENCH {json.dumps(result)}")
    return result


def run_cache(rows: int = 100_000, n_queries: int = 512, n_hot: int = 16,
              batch: int = 64, cache_mb: int = 64, out_path: str = None,
              smoke: bool = False) -> dict:
    """Semantic-cache mode (DESIGN.md §9): the Zipfian cache sweep.

    A ``zipf_rects`` hot-rect stream (repeats = exact hits, nested subsets
    = containment partials, per the "Benchmarking Learned Indexes" advice
    to gate on a skewed mix rather than uniform rects) is answered three
    ways on one airline index: uncached (the baseline + bit-identity
    oracle), a cold cached pass (admissions + partials), and a warm cached
    pass (the steady state the QPS claim is about).  Then the §9.3 MVCC
    drill: a pinned reader on a background-compacting index must answer
    bit-identically to pin time across a real epoch handoff, and the old
    epoch must stay alive until release.

    ``smoke`` turns the gates into hard assertions for CI: cache-on ≡
    cache-off flat hits, ``cache_hit_rate > 0``, and pinned-reader
    agreement.  Results land in the ``cache`` section of
    ``BENCH_queries.json``; other sections are merge-preserved.
    """
    import sys
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tests"))
    from workloads import zipf_rects

    ds = dataset("airline", rows)
    rects = zipf_rects(ds.data, n=n_queries, n_hot=n_hot, seed=PCFG.seed,
                       sample_cap=min(rows, 100_000))
    idx = COAXIndex(ds.data)

    ex0 = BatchQueryExecutor(idx, max_batch=batch)
    want = ex0.execute(rects)                    # warm pass
    ex0.reset_stats()
    t0 = time.perf_counter()
    ex0.execute(rects)
    uncached_qps = len(rects) / (time.perf_counter() - t0)
    emit("cache/airline/uncached_qps", uncached_qps,
         f"rows={rows},queries={len(rects)},n_hot={n_hot},batch={batch}")

    idx.attach_cache(byte_budget=cache_mb << 20)
    ex = BatchQueryExecutor(idx, max_batch=batch)
    cold = ex.execute(rects)                     # populates + partial hits
    assert all(np.array_equal(g, w) for g, w in zip(cold, want)), \
        "cold cached pass disagrees with the uncached oracle"
    cold_stats = ex.stats()
    ex.reset_stats()
    t0 = time.perf_counter()
    warm = ex.execute(rects)
    cached_qps = len(rects) / (time.perf_counter() - t0)
    assert all(np.array_equal(g, w) for g, w in zip(warm, want)), \
        "warm cached pass disagrees with the uncached oracle"
    s = ex.stats()
    result = {
        "dataset": "airline", "rows": rows, "n_queries": len(rects),
        "n_hot": n_hot, "batch": batch, "cache_mb": cache_mb,
        "uncached_qps": uncached_qps, "cached_qps": cached_qps,
        "cache_speedup": cached_qps / uncached_qps,
        "cold_hit_rate": cold_stats["cache_hit_rate"],
        "warm_hit_rate": s["cache_hit_rate"],
        "cache_bytes": s["cache_bytes"],
        "cache": idx.cache.describe(),
    }
    emit("cache/airline/cached_qps", cached_qps,
         f"speedup={result['cache_speedup']:.2f}x,"
         f"warm_hit_rate={s['cache_hit_rate']:.3f},"
         f"cache_bytes={s['cache_bytes']}")

    # ---------------- §9.3 MVCC drill: pin across a real handoff -------- #
    mvcc_rows = min(rows, 20_000)
    bg = COAXIndex(ds.data[:mvcc_rows],
                   CoaxConfig(background_compact=True, compact_min_delta=512,
                              compact_delta_frac=0.01, compact_check_rows=32))
    mvcc_rects = rects[:min(64, len(rects))]
    pin = bg.pin_epoch()
    pinned_want = pin.query_batch_split(mvcc_rects)
    rng = np.random.default_rng(PCFG.seed)
    t0 = time.perf_counter()
    while bg.background_compactions < 1:
        bg.insert(ds.data[rng.integers(0, mvcc_rows, 128)])
        bg.poll_handoff(wait=True)
    bg.finish_handoff()
    handoff_s = time.perf_counter() - t0
    pinned_got = pin.query_batch_split(mvcc_rects)
    mvcc_ok = all(np.array_equal(g, w)
                  for g, w in zip(pinned_got, pinned_want))
    assert mvcc_ok, "pinned reader diverged across the background handoff"
    assert bg.epoch > pin.epoch
    pin.release()
    result["mvcc"] = {
        "pinned_agreement": mvcc_ok, "pinned_epoch": pin.epoch,
        "live_epoch": bg.epoch, "handoffs": bg.background_compactions,
        "handoff_drive_s": handoff_s,
    }
    emit("cache/airline/mvcc_pin", 1.0,
         f"pinned@{pin.epoch} bit-identical across handoff to "
         f"epoch {bg.epoch} ({len(mvcc_rects)} rects)")

    if smoke:
        assert s["cache_hit_rate"] > 0, "warm pass produced no cache hits"
        emit("cache/airline/smoke", 1.0,
             f"cache-on == cache-off ({len(rects)} rects), "
             f"warm_hit_rate={s['cache_hit_rate']:.3f}, mvcc pin ok")

    _write_bench_section(out_path, "BENCH_queries.json", "cache", result)
    print(f"BENCH {json.dumps(result)}")
    return result


def run_telemetry(rows: int = 100_000, n_queries: int = 512, batch: int = 64,
                  out_path: str = None, smoke: bool = False,
                  backend: str = "numpy") -> dict:
    """Telemetry mode (DESIGN.md §10): the observability plane's own gate.

    Drives one airline read sweep twice — tracing OFF then tracing ON
    (best-of-3 each, same rects, same executor) — and a short mixed
    write phase with background compaction, then reports:

    * per-stage wall breakdown (probe/search/filter/merge/delta_scan/
      cache/dispatch/transfer/fsync) from ``coax_stage_seconds``;
    * the tracing overhead ratio (instrumented vs not);
    * trace structure health (``Tracer.validate``) + exposition
      round-trip (``render_text`` -> ``parse_text_exposition``);
    * serving-pause attribution from the §10.3 watchdog.

    ``smoke`` turns the §10.4 budget into hard CI assertions: overhead
    ≤5% QPS, the trace validates, the exposition parses, and tracing-on
    answers stay bit-identical to tracing-off.  Results land in the
    ``telemetry`` section of ``BENCH_queries.json``.
    """
    from repro import obs
    from repro.engine import QueryServer

    ds = dataset("airline", rows)
    rects = np.asarray(queries("airline", rows, n_queries, PCFG.knn_k))
    idx = COAXIndex(ds.data)
    ex = BatchQueryExecutor(idx, max_batch=batch, backend=backend)
    want = ex.execute(rects)                     # warm (jit, page-in)

    def timed():
        t0 = time.perf_counter()
        got = ex.execute(rects)
        return len(rects) / (time.perf_counter() - t0), got

    # interleave tracing-on/off samples so machine drift (frequency
    # scaling, page cache, sibling load) cancels instead of landing
    # entirely on one side of the §10.4 overhead ratio
    tr = obs.enable_tracing(capacity=65536)
    obs.set_tracer(None)
    try:
        off_s, on_s = [], []
        got_off = got_on = None
        for _ in range(5):
            obs.set_tracer(None)
            q, got_off = timed()
            off_s.append(q)
            obs.set_tracer(tr)
            q, got_on = timed()
            on_s.append(q)
        qps_off, qps_on = max(off_s), max(on_s)
        identical = all(np.array_equal(a, b) for a, b in zip(got_on, want)) \
            and all(np.array_equal(a, b) for a, b in zip(got_off, want))
        overhead = 1.0 - qps_on / qps_off

        # ------- short mixed phase: pause attribution under compaction --- #
        bg = COAXIndex(ds.data[:min(rows, 30_000)].copy(),
                       CoaxConfig(background_compact=True,
                                  compact_min_delta=512,
                                  compact_delta_frac=0.01,
                                  compact_check_rows=64))
        srv = QueryServer(bg, max_batch=batch)
        rng = np.random.default_rng(PCFG.seed)
        for _ in range(2):                       # enough waves to cross the
            for start in range(0, len(rects), batch):   # compaction trigger
                srv.insert(ds.data[rng.integers(0, len(ds.data), 128)])
                for r in rects[start:start + batch]:
                    srv.submit(r)
                srv.drain()
        bg.finish_handoff()
        ss = srv.stats()
        # validate AFTER the mixed phase so compaction/WAL spans are in
        # scope too, not just the read sweep's wave spans
        ok, problems = tr.validate()

        text = obs.get_registry().render_text()
        parsed = obs.parse_text_exposition(text)

        stages = {}
        hist = obs.stage_hist()
        for series in obs.get_registry().snapshot() \
                         .get("coax_stage_seconds", {}).get("series", []):
            lab = series["labels"]
            summ = hist.summary(**lab)
            if summ["count"]:
                stages[f"{lab['stage']}/{lab['backend']}"] = {
                    "count": summ["count"], "total_s": summ["sum"],
                    "p50_us": summ["p50"] * 1e6, "p99_us": summ["p99"] * 1e6,
                }

        result = {
            "dataset": "airline", "rows": rows, "n_queries": len(rects),
            "batch": batch, "backend": backend,
            "qps_tracing_off": qps_off, "qps_tracing_on": qps_on,
            "tracing_overhead": overhead,
            "bit_identical": bool(identical),
            "trace_valid": bool(ok), "trace_problems": problems[:8],
            "trace_events": len(tr.events()), "trace_dropped": tr.dropped,
            "exposition_families": len(parsed),
            "stages": stages,
            "pauses": {
                "count": int(ss.get("pauses", 0)),
                "median_gap_s": ss.get("pause_median_gap_s", 0.0),
                "last_culprit": ss.get("last_pause_culprit"),
            },
            "compactions": {
                "background": bg.background_compactions,
                "handoff_s": bg.last_handoff_s,
            },
        }
        emit("telemetry/airline/overhead", overhead * 100,
             f"qps_off={qps_off:.0f},qps_on={qps_on:.0f},"
             f"events={result['trace_events']},"
             f"families={result['exposition_families']}")
        for k, v in sorted(stages.items()):
            emit(f"telemetry/airline/stage/{k}", v["p50_us"],
                 f"count={v['count']},total_s={v['total_s']:.4f}")

        if smoke:
            assert identical, \
                "tracing-on answers diverged from tracing-off"
            assert ok, f"trace failed validation: {problems[:4]}"
            assert parsed, "text exposition failed to parse"
            assert "coax_stage_seconds" in parsed, \
                "stage histogram missing from exposition"
            assert overhead <= 0.05, \
                f"tracing overhead {overhead:.1%} exceeds the 5% budget"
            emit("telemetry/airline/smoke", 1.0,
                 f"overhead={overhead:.2%}<=5%, trace ok, "
                 f"{len(parsed)} families parsed")
    finally:
        obs.disable_tracing()

    _write_bench_section(out_path, "BENCH_queries.json", "telemetry", result)
    print(f"BENCH {json.dumps(result)}")
    return result


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", action="store_true",
                    help="throughput mode: QPS vs batch size + BENCH_queries.json")
    ap.add_argument("--mixed", action="store_true",
                    help="read/write mode: insert-ratio sweep + BENCH_updates.json")
    ap.add_argument("--shards", type=str, default=None, metavar="K[,K...]",
                    help="sharded mode: scatter-gather scaling sweep over "
                         "these shard counts (DESIGN.md §6)")
    ap.add_argument("--recover", action="store_true",
                    help="durability mode: snapshot/save/recovery costs + "
                         "BENCH_storage.json (DESIGN.md §7)")
    ap.add_argument("--failover", action="store_true",
                    help="replication mode: WAL shipping under faults, "
                         "promotion drills + BENCH_storage.json (DESIGN.md §8)")
    ap.add_argument("--cache", action="store_true",
                    help="semantic-cache mode: Zipfian hot-rect sweep + "
                         "MVCC pin drill + BENCH_queries.json (DESIGN.md §9)")
    ap.add_argument("--telemetry", action="store_true",
                    help="telemetry mode: per-stage breakdown, tracing "
                         "overhead gate + BENCH_queries.json (DESIGN.md §10)")
    ap.add_argument("--backend", choices=("numpy", "device", "both"),
                    default="both", help="which query_batch backend(s) to sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep + hard throughput/agreement asserts (CI)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--queries", type=int, default=None)
    args = ap.parse_args(argv)
    if args.telemetry:
        run_telemetry(rows=args.rows or 100_000,
                      n_queries=args.queries or (256 if args.smoke else 512),
                      smoke=args.smoke,
                      backend="numpy" if args.backend == "both"
                      else args.backend)
    elif args.cache:
        run_cache(rows=args.rows or 100_000,
                  n_queries=args.queries or (192 if args.smoke else 512),
                  smoke=args.smoke)
    elif args.failover:
        run_failover(rows=args.rows or 50_000,
                     n_queries=args.queries or (48 if args.smoke else 96),
                     smoke=args.smoke)
    elif args.recover:
        run_recover(rows=args.rows or 100_000,
                    n_queries=args.queries or (64 if args.smoke else 128),
                    smoke=args.smoke)
    elif args.shards:
        counts = tuple(int(k) for k in args.shards.split(","))
        run_sharded(rows=args.rows or 100_000,
                    n_queries=args.queries or (64 if args.smoke else 256),
                    shard_counts=counts, smoke=args.smoke,
                    # --backend both is the batch-mode default; the sharded
                    # sweep runs one backend per invocation
                    backend="numpy" if args.backend == "both" else args.backend)
    elif args.mixed:
        # smoke still sweeps enough waves (256/64 = 4 per ratio) for the
        # serving-pause profile to mean something
        run_mixed(rows=args.rows or 50_000,
                  n_queries=args.queries or (256 if args.smoke else 192),
                  smoke=args.smoke)
    elif args.batch:
        run_batch(rows=args.rows or 100_000,
                  n_queries=args.queries or (64 if args.smoke else 256),
                  backend=args.backend, smoke=args.smoke)
    else:
        run(rows=args.rows, n_queries=args.queries)


if __name__ == "__main__":
    main()
