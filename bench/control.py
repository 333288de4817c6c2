#!/usr/bin/env python3
"""The control of the answer check, at a cell's own size, over seeds.

    python bench/control.py --workload <cell> --seeds 11,12,13

For each seed the table and query pool are made as a run makes them, and
as many rects as a run compares (``run.SAMPLE + run.LARGEST``, in the
window's order) are answered by the float32 full scan and by its
bfloat16 control, put in the program's place.  ``compare`` then judges
both against the float32 scan: the reference must read 0 wrong answers,
and the control must read more than the limit of 0, or the check could
not tell a program that computes in bfloat16 from a correct one.
Benchmark runs do not run this; it sets the upper reading in ``PERF.md``.
One JSON line per seed; exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def reading(cell, seed: int) -> dict:
    import jax.numpy as jnp

    from bench import run, seeding
    from bench.reference import FullScan, compare

    t = time.perf_counter()
    cols, rects, phases = run.make_inputs(cell, seed)
    order = seeding.np_rng(seed, seeding.ORDER).permutation(rects.shape[0])
    sel = rects[order[:run.SAMPLE + run.LARGEST]]
    ref, ctrl = FullScan(cols), FullScan(cols, jnp.bfloat16)
    try:
        want = ref.counts(sel)
        itself = compare(ref, sel, want,
                         lambda qs: ref.ids(sel[qs], want[qs]))
        got = ctrl.counts(sel)
        control = compare(ref, sel, got,
                          lambda qs: ctrl.ids(sel[qs], got[qs]))
    finally:
        ref.close()
        ctrl.close()
    return {"workload": cell.name, "seed": seed, "compared": int(sel.shape[0]),
            "reference_vs_itself": itself["wrong_answers"],
            "control_wrong_answers": control["wrong_answers"],
            "seconds": time.perf_counter() - t, **phases}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated non-negative seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import chip, spec
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    cell = spec.load_cell(args.workload, ROOT)
    try:
        chip.check_devices(jax.devices(), cell.chips)
    except chip.Refused as e:
        print(e, file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(reading(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    sys.exit(main())
