"""Grid-file and helper invariants (paper §6)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import FullScan, GridFile, fit_cells_per_dim, gather_ranges
from repro.core.types import full_rect, rect_contains


def test_gather_ranges_basic():
    out = gather_ranges(np.array([0, 5, 9]), np.array([2, 5, 12]))
    assert out.tolist() == [0, 1, 9, 10, 11]
    assert gather_ranges(np.array([3]), np.array([3])).size == 0


@given(st.lists(st.tuples(st.integers(0, 50), st.integers(0, 50)), max_size=8))
@settings(max_examples=50, deadline=None)
def test_gather_ranges_property(pairs):
    los = np.array([min(a, b) for a, b in pairs], np.int64)
    his = np.array([max(a, b) for a, b in pairs], np.int64)
    got = gather_ranges(los, his)
    want = np.concatenate([np.arange(l, h) for l, h in zip(los, his)]) if pairs else np.empty(0)
    assert np.array_equal(got, want.astype(np.int64))


def test_fit_cells_per_dim():
    assert fit_cells_per_dim(2, 100) == 10
    assert fit_cells_per_dim(3, 27) == 3
    assert fit_cells_per_dim(0, 5) == 1
    assert fit_cells_per_dim(4, 1) == 1


@pytest.mark.parametrize("sort_dim", [None, 0, 2])
@pytest.mark.parametrize("quantile", [True, False])
def test_gridfile_equals_fullscan(sort_dim, quantile):
    rng = np.random.default_rng(1)
    data = rng.normal(0, 10, size=(5_000, 3)).astype(np.float32)
    gf = GridFile(data, index_dims=[0, 1, 2], cells_per_dim=6,
                  sort_dim=sort_dim, quantile=quantile)
    fs = FullScan(data)
    for seed in range(8):
        r = np.sort(rng.normal(0, 10, size=(3, 2)), axis=1)
        assert np.array_equal(gf.query(r, r), fs.query(r))


def test_gridfile_empty_data():
    data = np.zeros((0, 3), np.float32)
    gf = GridFile(data, index_dims=[0, 1, 2], cells_per_dim=4, sort_dim=0)
    r = full_rect(3)
    assert gf.query(r, r).size == 0


def test_gridfile_stats_and_memory():
    rng = np.random.default_rng(2)
    data = rng.uniform(0, 1, size=(2_000, 2)).astype(np.float32)
    gf = GridFile(data, index_dims=[0, 1], cells_per_dim=8, sort_dim=1)
    r = np.array([[0.2, 0.4], [0.1, 0.9]])
    out = gf.query(r, r)
    st_ = gf.last_query_stats
    assert st_.rows_matched == out.size
    assert st_.rows_scanned >= st_.rows_matched
    assert gf.memory_footprint() > 0
    # sorted dim removes one grid dimension
    assert len(gf.grid_dims) == 1


def test_gridfile_duplicate_values_ok():
    """Quantile edges collapse on heavily-duplicated columns; queries must
    still be exact."""
    rng = np.random.default_rng(3)
    data = np.stack([
        rng.integers(0, 3, 3_000).astype(np.float32),
        rng.normal(0, 1, 3_000).astype(np.float32),
    ], axis=1)
    gf = GridFile(data, index_dims=[0, 1], cells_per_dim=8, sort_dim=1)
    fs = FullScan(data)
    r = np.array([[1.0, 2.0 + 1e-6], [-0.5, 0.5]])
    assert np.array_equal(gf.query(r, r), fs.query(r))
