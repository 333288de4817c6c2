"""The reference is exact and its bfloat16 control fails, at test size.

On the chip the same readings come from ``bench/control.py`` at each
cell's own size.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control
from bench.reference import FullScan, compare
from bench.tests.cells import SEED, table_and_pool, tiny_cell


def _brute(cols, rects):
    rows = cols.T
    return [np.flatnonzero(np.all((rows >= r[:, 0]) & (rows < r[:, 1]),
                                  axis=1)) for r in rects]


@pytest.mark.parametrize("table", ["airline", "osm"])
def test_reference_is_exact_and_control_is_not(table):
    cols, rects = table_and_pool(table, 20_000, 64)
    want = _brute(cols, rects)
    ref = FullScan(cols)
    got = ref.ids(rects, ref.counts(rects))
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    assert compare(ref, rects, [w.size for w in want],
                   lambda qs: [want[q] for q in qs])["wrong_answers"] == 0
    ctrl = FullScan(cols, jnp.bfloat16)
    sizes = ctrl.counts(rects)
    res = compare(ref, rects, sizes, lambda qs: ctrl.ids(rects[qs], sizes[qs]))
    assert res["wrong_answers"] > 0
    assert compare(ref, rects, [-1] * len(want),
                   lambda qs: [])["unanswered"] == len(want)


@pytest.mark.parametrize("workload", ["airline-80m.knn10-closed",
                                      "osm-105m.knn10-open"])
def test_control_reading_fails_the_limit(workload):
    r = control.reading(tiny_cell(workload, n_rows=20_000, pool=256), SEED)
    assert r["compared"] == 256
    assert r["reference_vs_itself"] == 0
    assert r["control_wrong_answers"] > 0
