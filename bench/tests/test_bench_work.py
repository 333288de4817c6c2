"""The benchmark's count of candidate rows, which sets the bytes the
kernel's roofline divides by, is the numpy backend's ``rows_scanned``."""
import numpy as np
import pytest

from bench import work
from bench.tests.cells import table_and_pool
from repro.core import COAXIndex


@pytest.mark.parametrize("table", ["airline", "osm"])
def test_candidate_rows_equal_numpy_rows_scanned(table):
    cols, rects = table_and_pool(table, 30_000, 96)
    index = COAXIndex(np.ascontiguousarray(cols.T))
    assert index.outlier.n_rows and index.primary.n_rows
    cand = work.candidate_rows(index, rects)
    index.query_batch(rects)
    assert int(cand.sum()) == index.last_batch_stats.rows_scanned
    for i in range(0, rects.shape[0], 16):       # and query by query
        index.query_batch(rects[i:i + 1])
        assert cand[i] == index.last_batch_stats.rows_scanned
    assert (cand >= 10).all()                    # K=10 rows in every rect


def test_needed_bytes_counts_rows_and_ids():
    assert work.needed_bytes(np.array([3, 5]), np.array([1, 2]), 8) == \
        8 * 8 * 4 + 3 * 4
