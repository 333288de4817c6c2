#!/usr/bin/env python3
"""Chip smoke test: exact COAX query waves served from the Pallas kernel.

Runs the index's main serving path once, in one process, on one TPU chip:
``QueryServer(max_batch=64)`` over a ``COAXIndex(backend="device")`` built
from seeded airline data (the paper's 8-column schema, ``data/synth.py``).
Four phases share the server:

  a. kNN-rect queries at K=10 (the narrow mix the device answers in full);
  b. kNN-rect queries at K=100 (the paper's §8.1.2 mix);
  c. an insert and a delete batch through ``srv.insert`` / ``srv.delete``,
     so the delta and tombstone segments run on the device, then queries;
  d. one ``compact()`` (new epoch: re-upload, jit-cache adoption), then
     queries again.

Every phase is checked against the same index's numpy backend (an
independent host path) query by query, and 8 queries per phase against a
full scan of ``live_rows()``.  The drain itself verifies that the device's
exact per-query counts equal the host re-answer of every query over
``hit_cap``.  The run fails — non-zero exit, no result line — when JAX sees
no TPU, when the plan would not run the compiled Pallas kernel, when any
wave fell back to the host, when kernel dispatches differ from device
waves, or on any mismatch.  Wave latencies printed here are
single-run observations, not a benchmark.

Usage:  python chip_smoke.py [--rows 20000000] [--seed 7]

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PAPER_ROWS = 80_000_000          # airline, paper Table 1
FULLSCAN_CHECKS = 8              # queries per phase checked by full scan


def _log(**kv) -> None:
    print(json.dumps(kv, default=float), flush=True)


def _fullscan(rows, ids, rect):
    """Sorted ids of ``rows`` inside the half-open ``rect``, one column at
    a time (f64 compare, as the numpy backend does)."""
    import numpy as np

    keep = np.ones(rows.shape[0], bool)
    for j in range(rows.shape[1]):
        col = rows[:, j].astype(np.float64)
        keep &= (col >= rect[j, 0]) & (col < rect[j, 1])
    return np.sort(ids[keep])


def run_phases(rows: int, seed: int = 7, n_queries: int = 256,
               device_opts=None, log=_log) -> dict:
    """Build, serve and check the four phases; returns a summary dict.
    Raises on any mismatch, fallback or dispatch-count violation, and when
    the plan would not run the Pallas kernel in the platform's own mode."""
    import jax
    import numpy as np

    from repro import obs
    from repro.core import COAXIndex, CoaxConfig
    from repro.data import knn_rect_queries, make_airline
    from repro.engine import QueryServer, split_hits
    from repro.kernels._platform import resolve_interpret

    t = time.perf_counter()
    ds = make_airline(rows, seed=seed)
    gen_s = time.perf_counter() - t
    t = time.perf_counter()
    # compaction happens where phase d asks for it, not on a size trigger
    idx = COAXIndex(ds.data, CoaxConfig(auto_compact=False),
                    backend="device", device_opts=device_opts)
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    plan = idx.device_plan()
    jax.block_until_ready(jax.live_arrays())
    upload_s = time.perf_counter() - t
    if not plan.use_pallas or plan.interpret != resolve_interpret(None):
        raise RuntimeError(f"plan runs use_pallas={plan.use_pallas} "
                           f"interpret={plan.interpret}, not the Pallas "
                           "kernel in the platform's mode")
    log(stage="setup", rows=rows, seed=seed, datagen_s=gen_s,
        build_s=build_s, upload_s=upload_s,
        hbm_resident_bytes=plan.bytes_h2d, use_pallas=plan.use_pallas,
        interpret=plan.interpret, primary_rows=idx.primary.n_rows,
        outlier_rows=idx.outlier.n_rows)

    srv = QueryServer(idx, max_batch=64, backend="device")
    rng = np.random.default_rng(seed)
    rects10 = knn_rect_queries(ds.data, n_queries, 10, seed=seed)
    rects100 = knn_rect_queries(ds.data, n_queries, 100, seed=seed + 1)
    summary = {"phases": {}, "use_pallas": plan.use_pallas,
               "interpret": plan.interpret}
    tracer = obs.enable_tracing()      # device.dispatch spans time compiles

    def serve(name, rects):
        ex = srv.executor
        s0, d0 = ex.stats(), idx.device_stats()["dispatches"]
        c0 = idx.device_stats()["compile_count"]
        tracer.clear()
        t0 = time.perf_counter()
        qids = srv.submit_many(rects)
        res = srv.drain()
        wall = time.perf_counter() - t0
        s1, ds1 = ex.stats(), idx.device_stats()
        n_waves = s1["waves"] - s0["waves"]
        waves = ex.wave_stats[-n_waves:] if n_waves else []
        compile_s = sum(e["t1"] - e["t0"] for e in tracer.events()
                        if e["name"] == "device.dispatch"
                        and e["args"].get("compiled"))
        dev_waves = sum(w.backend == "device" for w in waves)
        fallbacks = s1["device_fallbacks"] - s0["device_fallbacks"]
        dispatches = ds1["dispatches"] - d0
        if fallbacks or dev_waves != len(waves):
            raise RuntimeError(f"{name}: {fallbacks} fallback waves")
        if dispatches != dev_waves:
            raise RuntimeError(f"{name}: {dispatches} dispatches for "
                               f"{dev_waves} device waves")
        # the same index's numpy backend, query by query
        idx.backend = "numpy"
        q_n, r_n = idx.query_batch(rects)
        idx.backend = "device"
        want = split_hits(q_n, r_n, rects.shape[0])
        for i, qid in enumerate(qids):
            if not np.array_equal(res[qid], want[i]):
                raise RuntimeError(
                    f"{name}: query {i} device {res[qid].size} hits != "
                    f"numpy {want[i].size}")
        live, ids = idx.live_rows()
        for i in rng.choice(len(qids), min(FULLSCAN_CHECKS, len(qids)),
                            replace=False):
            if not np.array_equal(res[qids[i]], _fullscan(live, ids, rects[i])):
                raise RuntimeError(f"{name}: query {i} differs from full scan")
        counts = np.array([want[i].size for i in range(len(qids))])
        lat = np.array([w.latency_s for w in waves])
        entry = {
            "waves": len(waves), "device_waves": dev_waves,
            "dispatches": dispatches, "fallback_waves": fallbacks,
            "hit_overflows": s1["hit_overflows"] - s0["hit_overflows"],
            "hits_median": float(np.median(counts)),
            "hits_max": int(counts.max()),
            "compiled_shapes": ds1["compile_count"] - c0,
            "compile_s": compile_s,
            "wall_s": wall,
            "wave_latency_s_not_a_benchmark": {
                "first": float(lat[0]), "median": float(np.median(lat)),
                "max": float(lat.max())},
            "exact_vs_numpy": len(qids), "exact_vs_fullscan":
                min(FULLSCAN_CHECKS, len(qids)),
        }
        summary["phases"][name] = entry
        log(stage="phase", phase=name, **entry)
        return res, qids

    res_a, qids_a = serve("a_knn10", rects10)
    serve("b_knn100", rects100)

    # c: writes through the server; a row at each rect's centre hits it,
    # and the first hit of each phase-a query is deleted
    mid = rects10.mean(axis=2).astype(np.float32)
    fresh = make_airline(max(n_queries, 1024), seed=seed + 2).data
    srv.insert(np.concatenate([mid, fresh]))
    gone = np.unique([res_a[q][0] for q in qids_a if res_a[q].size])
    srv.delete(gone)
    srv.flush_writes()
    if not (idx.delta_rows and idx.tombstone_count):
        raise RuntimeError("phase c left no delta rows or tombstones")
    serve("c_writes", rects10)

    # d: one compaction -> new epoch, plan re-upload, jit cache adopted
    epoch = idx.epoch
    t = time.perf_counter()
    idx.compact()
    compact_s = time.perf_counter() - t
    if idx.epoch == epoch or idx.delta_rows or idx.tombstone_count:
        raise RuntimeError("compaction did not fold the delta in")
    serve("d_compacted", rects10)
    summary["phases"]["d_compacted"]["compact_s"] = compact_s
    obs.disable_tracing()
    st = idx.device_stats()
    summary.update(compile_count=st["compile_count"],
                   dispatches=st["dispatches"], bytes_h2d=st["bytes_h2d"],
                   bytes_d2h=st["bytes_d2h"], rows=rows)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=20_000_000,
                    help="airline rows (paper: 80,000,000)")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 1
    _log(stage="device", platform=dev.platform, device_kind=dev.device_kind,
         count=len(devices), jax=jax.__version__, compile_cache=cache_dir,
         rows=args.rows, paper_rows=PAPER_ROWS,
         reduced=(None if args.rows >= PAPER_ROWS else
                  f"rows {args.rows} of the paper's {PAPER_ROWS}: "
                  "host build time"))
    t = time.perf_counter()
    summary = run_phases(args.rows, args.seed)
    if not summary["use_pallas"] or summary["interpret"]:
        print("the waves did not run the compiled Pallas kernel",
              file=sys.stderr)
        return 1
    mem = dev.memory_stats() or {}
    _log(stage="done", total_s=time.perf_counter() - t,
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         bytes_limit=mem.get("bytes_limit"),
         compile_count=summary["compile_count"],
         dispatches=summary["dispatches"],
         bytes_h2d=summary["bytes_h2d"], bytes_d2h=summary["bytes_d2h"],
         hit_overflows={k: v["hit_overflows"]
                        for k, v in summary["phases"].items()})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
