#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip, in one process.

    python bench/sweep.py --workload <open cell> --seed <n> --seconds <s> \\
        --fractions 0.4,0.55,0.7,0.8,0.9,1.0

One set-up as a run makes it; then a closed loop (256 outstanding) gives
the capacity, and the open loop is driven at each fraction of that
capacity for ``--seconds``.  Each point prints a JSON line: the rate, the
median and 95th percentile latency, and the 95th percentile in the first
and last third of the arrivals, which grows when the backlog does.  The
knee is the highest rate that meets the cell's latency limit without a
growing backlog; the cell runs at 0.8 of it, written into its traffic
file by hand (``PERF.md`` gives the sweep).  Runs of the benchmark do not
run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fractions", default="0.4,0.55,0.7,0.8,0.9,1.0")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import chip, run, seeding, spec
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    cell = spec.load_cell(args.workload, ROOT)
    try:
        chip.check_devices(jax.devices(), cell.chips)
    except chip.Refused as e:
        print(e, file=sys.stderr)
        return 1
    st = run.set_up(cell, args.seed, t_start, run._log)
    order = seeding.np_rng(args.seed, seeding.ORDER).permutation(
        st.rects.shape[0])

    mark = run._mark(False)
    closed = spec.load_module(ROOT, "traffic", "closed_loop").run(
        st.srv, st.rects, order, args.seconds, {"outstanding": 256}, None,
        mark)
    cap = closed["metrics"]["answered_qps"]
    run._log(stage="closed", capacity_qps=cap, **closed["log"])
    open_loop = spec.load_module(ROOT, "traffic", "open_loop")
    for f in (float(x) for x in args.fractions.split(",")):
        params = copy.deepcopy(cell.traffic["arrivals"])
        params["rate_qps"] = f * cap
        t0 = time.perf_counter()
        w = open_loop.run(st.srv, st.rects, order, args.seconds, params,
                          seeding.np_rng(args.seed, seeding.ARRIVALS), mark)
        due = np.array(sorted(w["rect_of"]))
        lat = []
        for part in np.array_split(due, 3):
            # latency per third of the arrivals, in submit (= due) order
            part_lat = [w["latency_s"][q] for q in part]
            lat.append(float(np.percentile(part_lat, 95)) * 1e3)
        run._log(stage="open", fraction=f, rate_qps=params["rate_qps"],
                 **w["metrics"], p95_first_third_ms=lat[0],
                 p95_last_third_ms=lat[-1], wall_s=time.perf_counter() - t0,
                 **w["log"])
    return 0


if __name__ == "__main__":
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    sys.exit(main())
