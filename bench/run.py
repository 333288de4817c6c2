#!/usr/bin/env python3
"""One benchmark run of one cell of ``BENCHMARK.json``, on one TPU.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from process start to the first measured
query): the cell's table is made on the device from the seed
(``bench/tables``), its query pool likewise (``bench/traffic``); the table
is copied to the host and every benchmark array is freed; the program
builds ``COAXIndex`` from it, uploads its device plan, and serves every
wave shape the cell's traffic can meet once through ``QueryServer`` (each
bucket of ``warm_batches``, with and without the outlier segment) so that
nothing compiles inside the window.

Window: the traffic's arrival module drives ``QueryServer`` for
``--seconds``.  With ``--trace 1`` the window is traced (``jax.profiler``
plus the program's ``repro.obs`` spans) for at most ``TRACE_SECONDS``, and
the per-layer metrics are reported instead of the end-to-end ones.
Stopping the profiler takes about 1.6 s for each traced second on a TPU
v5e (92 s after a 51 s airline window, 59 s after 30 s), so a traced
window as long as the untraced one would not end within six minutes.

Check: once the window has closed and the peak memory has been read, the
program is freed and a sample of the answers served in the window (drawn
from the seed, plus the largest) is compared with a plain full scan of the
table (``bench/reference.py``).  The numbers compared are printed with
their limits as the last lines of standard error and under ``checks``, the
last key of the result, which is the last line of standard output.

The run exits non-zero with no result line when JAX's first device is not
a TPU, when it has fewer chips than the cell asks for, when the device
kind has no peaks in ``bench/peaks.json``, when the program is missing,
and when the plan would not run the compiled Pallas kernel.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                         # noqa: E402
import contextlib                                       # noqa: E402
import gc                                               # noqa: E402
import json                                             # noqa: E402
import shutil                                           # noqa: E402
import sys                                              # noqa: E402
import tempfile                                         # noqa: E402
from pathlib import Path                                # noqa: E402
from types import SimpleNamespace                       # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SAMPLE = 512        # answers compared per run, drawn from the seed ...
LARGEST = 32        # ... plus the largest answers served
KERNEL = "coax_fused_scan"
TRACE_SECONDS = 30.0    # a traced window's longest (see above)


def _log(**kv) -> None:
    print(json.dumps(kv), flush=True)


def _dist(sizes) -> dict:
    import numpy as np

    s = np.asarray(sizes)
    if not s.size:
        return {"n": 0}
    return {"n": int(s.size), "median": float(np.median(s)),
            "p95": float(np.percentile(s, 95)), "max": int(s.max())}


def _mark(traced: bool):
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def make_inputs(cell, seed: int):
    """The cell's table and query pool from the seed, made on the device;
    returns the table's ``(D, N)`` host copy, the pool's rects and the
    phase times.  No benchmark array is left on the device."""
    import numpy as np

    from bench import seeding

    cfg, pool = cell.config, cell.traffic["pool"]
    phases = {}
    t = time.perf_counter()
    table = cell.module("tables", cfg["table"]).make(
        seeding.jax_key(seed, seeding.TABLE), n_rows=int(cfg["n_rows"]),
        **cfg.get("table_params", {}))
    table.block_until_ready()
    phases["table_s"] = time.perf_counter() - t
    t = time.perf_counter()
    rects = cell.module("traffic", pool["kind"]).make_pool(
        table, seeding.jax_key(seed, seeding.POOL), pool)
    phases["queries_s"] = time.perf_counter() - t
    t = time.perf_counter()
    cols = np.asarray(table)
    table.delete()
    phases["to_host_s"] = time.perf_counter() - t
    return cols, rects, phases


def set_up(cell, seed: int, t_start: float, log, kernel_check: bool = True):
    """Table, query pool, index, plan and warm-up; returns the served
    state and the set-up phases."""
    import jax
    import numpy as np

    from repro.core import COAXIndex
    from repro.engine import QueryServer
    from repro.kernels._platform import resolve_interpret

    cfg, traffic = cell.config, cell.traffic
    cols, rects, phases = make_inputs(cell, seed)   # (D, N): the reference's
    t = time.perf_counter()
    rows = np.ascontiguousarray(cols.T)             # (N, D): the program's
    phases["to_host_s"] += time.perf_counter() - t
    harness_bytes = sum(a.nbytes for a in jax.live_arrays())
    t = time.perf_counter()
    index = COAXIndex(rows)
    phases["build_s"] = time.perf_counter() - t
    srv = QueryServer(index, **cfg["server"])
    t = time.perf_counter()
    plan = index.device_plan()
    jax.block_until_ready(jax.live_arrays())
    phases["upload_s"] = time.perf_counter() - t
    if kernel_check and (not plan.use_pallas
                         or plan.interpret != resolve_interpret(None)):
        raise RuntimeError(f"plan runs use_pallas={plan.use_pallas} "
                           f"interpret={plan.interpret}, not the Pallas "
                           "kernel in the platform's mode")
    t = time.perf_counter()
    # each wave bucket twice: pool rects reach the outlier grid, and rects
    # past the table's largest values reach no outlier row, so the wave
    # program without the outlier segment is compiled here too
    top = np.nextafter(cols.max(axis=1), np.float32(np.inf))
    beyond = np.stack([top, np.nextafter(top, np.float32(np.inf))], axis=1)
    for b in traffic["warm_batches"]:
        for warm in (rects[:b], np.repeat(beyond[None], b, axis=0)):
            srv.submit_many(warm)
            srv.drain()
    phases["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log(stage="setup", setup_s=setup_s, **phases, rows=int(cfg["n_rows"]),
        pool=int(rects.shape[0]), harness_device_bytes_at_build=harness_bytes,
        primary_rows=index.primary.n_rows, outlier_rows=index.outlier.n_rows,
        hbm_resident_bytes=plan.bytes_h2d, use_pallas=plan.use_pallas,
        interpret=plan.interpret, hit_cap=plan.hit_cap,
        compiled_shapes=plan.compile_count)
    return SimpleNamespace(cols=cols, rows=rows, rects=rects, index=index,
                           srv=srv, plan=plan, setup_s=setup_s)


def serve_window(cell, st, seed: int, seconds: float, traced: bool, log):
    """Drive the window; returns the arrival module's record plus what the
    program counted and spent in it."""
    import jax

    from bench import seeding
    from repro import obs

    if traced:
        seconds = min(seconds, TRACE_SECONDS)
    arrivals = cell.traffic["arrivals"]
    drive = cell.module("traffic", arrivals["kind"])
    order = seeding.np_rng(seed, seeding.ORDER).permutation(st.rects.shape[0])
    ex = st.srv.executor
    s0, c0 = ex.stats(), st.plan.compile_count
    tracer = obs.enable_tracing() if traced else None
    logdir = tempfile.mkdtemp(prefix="coax-bench-trace-") if traced else None
    mark = _mark(traced)
    try:
        if traced:
            jax.profiler.start_trace(logdir, profiler_options=_trace_options())
        with mark("bench.window"):
            win = drive.run(st.srv, st.rects, order, seconds, arrivals,
                            seeding.np_rng(seed, seeding.ARRIVALS), mark)
        if traced:
            jax.profiler.stop_trace()
        s1 = ex.stats()
        win["served"] = {k: s1[k] - s0[k]
                         for k in ("queries", "waves", "hit_overflows",
                                   "device_fallbacks")}
        win["compiles_in_window"] = st.plan.compile_count - c0
        win["spans"] = tracer.events() if traced else []
        win["trace"] = None
        if traced:
            from bench import xplane

            win["trace"] = xplane.reduce(xplane.load(logdir), KERNEL)
    finally:
        if traced:
            obs.disable_tracing()
            shutil.rmtree(logdir, ignore_errors=True)
    sizes = [a.size for a in win["answers"].values()]
    log(stage="window", seconds=seconds, **win["log"], **win["served"],
        compiles_in_window=win["compiles_in_window"],
        result_rows=dict(_dist(sizes), over_hit_cap_share=(
            sum(s > st.plan.hit_cap for s in sizes) / len(sizes)
            if sizes else 0.0)))
    return win


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host annotations, not every call
    opts.raise_error_on_start_failure = True
    return opts


def check(st, win, seed: int, log) -> dict:
    """Compare a seeded sample of the window's answers, plus the largest,
    with the full-scan reference."""
    import jax
    import numpy as np

    from bench import seeding
    from bench.reference import FullScan, compare

    qids = np.array(sorted(win["rect_of"]), np.int64)
    size_of = {q: a.size for q, a in win["answers"].items()}
    rng = seeding.np_rng(seed, seeding.SAMPLE)
    pick = set(rng.choice(qids, min(SAMPLE, qids.size), replace=False)
               .tolist()) if qids.size else set()
    pick |= set(sorted(size_of, key=size_of.get)[-LARGEST:])
    pick = np.array(sorted(pick), np.int64)
    rects = st.rects[[win["rect_of"][q] for q in pick]]
    sizes = [size_of.get(q, -1) for q in pick]
    unanswered = sum(q not in win["answers"] for q in qids)
    left = sum(a.nbytes for a in jax.live_arrays())    # program freed?
    t = time.perf_counter()
    ref = FullScan(st.cols)
    try:
        res = compare(ref, rects, sizes,
                      lambda qs: [win["answers"][pick[i]] for i in qs])
    finally:
        ref.close()
    res["unanswered"] = unanswered
    res["reference_s"] = time.perf_counter() - t
    res["device_bytes_before_reference"] = left
    log(stage="reference", **res)
    return res


def run_cell(cell, seed: int, seconds: float, traced: bool, peaks, devices,
             t_start: float = T_START, log=_log,
             kernel_check: bool = True) -> dict:
    """Set up, serve the window, read the metrics, free the program, check
    the answers; returns the result line as a dict."""
    from bench import chip, spec, work

    st = set_up(cell, seed, t_start, log, kernel_check)
    win = serve_window(cell, st, seed, seconds, traced, log)
    device = chip.device_record(devices, cell.chips)
    n_rows = int(cell.config["n_rows"])
    if traced:
        rect_ids = [win["rect_of"][q] for q in win["answers"]]
        hits = [a.size for a in win["answers"].values()]
        import numpy as np

        # a query's candidate rows depend on its rect alone: count each
        # pool rect once, however often the window cycled through it
        uniq, inv = np.unique(rect_ids, return_inverse=True)
        cand = work.candidate_rows(st.index, st.rects[uniq])[inv]
        ctx = SimpleNamespace(
            trace=win["trace"], spans=win["spans"], served=win["served"],
            peaks=peaks, needed_bytes=work.needed_bytes(
                cand, np.asarray(hits), st.rows.shape[1]))
        metrics = spec.read_per_layer(cell, ctx)
        device.update(busy_s=win["trace"]["busy_s"],
                      window_s=win["trace"]["window_s"])
        log(stage="trace", kernel_s=win["trace"]["kernel_s"],
            kernel_events=win["trace"]["kernel_events"],
            needed_bytes=ctx.needed_bytes, candidate_rows=_dist(cand))
    else:
        values = dict(win["metrics"], setup_s=st.setup_s,
                      hbm_bytes_per_row=device["memory_peak_bytes"] / n_rows)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    # free the program before the reference runs on the device
    st.srv = st.index = st.plan = None
    gc.collect()
    res = check(st, win, seed, log)
    checks = {"wrong_answers": {"value": res["wrong_answers"], "limit": 0},
              "unanswered": {"value": res["unanswered"], "limit": 0}}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": int(win["attempted"]),
           "failed": res["wrong_answers"] + res["unanswered"],
           "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = win["trace"]["breakdown"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import chip, spec
    from repro.compile_cache import enable_compile_cache

    cell = spec.load_cell(args.workload, ROOT)
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    try:
        peaks = chip.check_devices(devices, cell.chips)
    except chip.Refused as e:
        print(e, file=sys.stderr)
        return 1
    _log(stage="device", platform=devices[0].platform,
         device_kind=devices[0].device_kind, count=len(devices),
         jax=jax.__version__, compile_cache=cache_dir, workload=cell.name,
         seed=args.seed, seconds=args.seconds, trace=args.trace)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks,
                   devices)
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)            # bench/ itself is not a package root
    sys.exit(main())
