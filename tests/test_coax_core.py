"""COAX core invariants: the index returns EXACTLY the full-scan result set
on every engine, margins/grouping reproduce Table 1, translation (Eq. 2)
over-approximates but never loses results."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    COAXIndex,
    CoaxConfig,
    ColumnFiles,
    FullScan,
    GridFile,
    STRTree,
    SoftFDConfig,
    UniformGrid,
    full_rect,
    point_rect,
    translate_rect,
)
from repro.core.softfd import bayes_linear_regress, BayesianLinearModel
from repro.data import knn_rect_queries, make_airline, make_generic_fd, make_osm


@pytest.fixture(scope="module")
def airline():
    return make_airline(60_000, seed=3)


@pytest.fixture(scope="module")
def osm():
    return make_osm(60_000, seed=3)


def _engines(data):
    return [
        COAXIndex(data),
        UniformGrid(data),
        ColumnFiles(data),
        STRTree(data),
    ]


@pytest.mark.parametrize("ds_name", ["airline", "osm"])
def test_all_engines_match_full_scan(ds_name, airline, osm):
    ds = {"airline": airline, "osm": osm}[ds_name]
    fs = FullScan(ds.data)
    engines = _engines(ds.data)
    rects = knn_rect_queries(ds.data, 15, 150, seed=1, sample_cap=10_000)
    for r in rects:
        truth = fs.query(r)
        for eng in engines:
            got = eng.query(r)
            assert np.array_equal(got, truth), (
                f"{eng.name} mismatch on {ds_name}: {len(got)} vs {len(truth)}")


def test_point_queries_match(airline):
    fs = FullScan(airline.data)
    cx = COAXIndex(airline.data)
    rng = np.random.default_rng(0)
    for i in rng.choice(airline.data.shape[0], 25, replace=False):
        r = point_rect(airline.data[i])
        truth = fs.query(r)
        assert i in truth
        assert np.array_equal(cx.query(r), truth)


def test_airline_grouping_matches_table1(airline):
    cx = COAXIndex(airline.data)
    # Table 1: two groups of three correlated dims; 2-4 indexed dims; ~92%.
    group_members = [set([g.predictor, *g.dependents]) for g in cx.groups]
    assert set(map(frozenset, group_members)) == {
        frozenset({0, 1, 2}), frozenset({3, 4, 5})}
    assert 2 <= len(cx.keep_dims) <= 4 + 2  # +2 uncorrelated cols always kept
    assert 0.85 <= cx.primary_ratio <= 0.97


def test_osm_grouping_matches_table1(osm):
    cx = COAXIndex(osm.data)
    assert [set([g.predictor, *g.dependents]) for g in cx.groups] == [{0, 1}]
    assert 0.65 <= cx.primary_ratio <= 0.85
    assert len(cx.keep_dims) == 3


def test_memory_footprint_reduction(airline):
    """Paper headline (§8.2.4): at EQUAL per-dim resolution, dropping the
    dependent dims (8 -> n-m-1 grid dims) shrinks the directory by orders of
    magnitude — cells go from c^8 to c^(n-m-1)."""
    c = 8
    cx = COAXIndex(airline.data, CoaxConfig(primary_cells_per_dim=c,
                                            outlier_cells_per_dim=2))
    ug = UniformGrid(airline.data, cells_per_dim=c)
    assert len(cx.primary.grid_dims) <= 4
    ratio = ug.memory_footprint() / cx.memory_footprint()
    assert ratio > 1e3, ratio  # 8 dims -> 3 grid dims at c=8: >= 3 orders


def test_unconstrained_query_returns_everything(airline):
    cx = COAXIndex(airline.data)
    out = cx.query(full_rect(airline.data.shape[1]))
    assert out.size == airline.data.shape[0]


def test_translation_never_loses_primary_rows(airline):
    """S-box contains R-box (paper §7.1): every primary row matching the
    original rect must fall inside the translated nav rect."""
    cx = COAXIndex(airline.data)
    rects = knn_rect_queries(airline.data, 10, 400, seed=5, sample_cap=10_000)
    prim_rows = cx.primary.rows
    for r in rects:
        nav = cx.translate(r)
        from repro.core import rect_contains
        full_hit = rect_contains(r, prim_rows)
        nav_full = np.stack([nav[:, 0], nav[:, 1]], axis=1)
        sub = prim_rows[:, cx.keep_dims]
        nav_hit = np.all((sub >= nav[:, 0]) & (sub <= nav[:, 1]), axis=1)
        # anything matching the full predicate must be inside the nav window
        assert not np.any(full_hit & ~nav_hit)


@settings(max_examples=25, deadline=None)
@given(
    n_dims=st.integers(3, 6),
    noise=st.floats(0.005, 0.05),
    outlier=st.floats(0.0, 0.25),
    seed=st.integers(0, 10_000),
)
def test_property_coax_equals_fullscan(n_dims, noise, outlier, seed):
    """Property: for arbitrary FD structure/noise/outlier mass, COAX returns
    the exact full-scan result set."""
    ds = make_generic_fd(4_000, n_dims, ((0, 1),), noise=noise,
                         outlier_frac=outlier, seed=seed)
    cfg = CoaxConfig(softfd=SoftFDConfig(sample_count=4_000, seed=seed))
    cx = COAXIndex(ds.data, cfg)
    fs = FullScan(ds.data)
    rects = knn_rect_queries(ds.data, 4, 60, seed=seed + 1, sample_cap=4_000)
    for r in rects:
        assert np.array_equal(cx.query(r), fs.query(r))


def test_bayesian_incremental_update_matches_batch():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 5, 2_000)
    y = 3.0 * x + 2.0 + rng.normal(0, 0.5, 2_000)
    m1, b1 = bayes_linear_regress(x, y)
    blm = BayesianLinearModel.empty()
    for lo in range(0, 2_000, 100):  # stream in chunks (paper §5 updates)
        blm.update(x[lo:lo + 100], y[lo:lo + 100])
    m2, b2 = blm.posterior_mean()
    assert abs(m1 - m2) < 1e-9 and abs(b1 - b2) < 1e-9
    assert abs(m1 - 3.0) < 0.05 and abs(b1 - 2.0) < 0.1


def test_supplied_groups_skip_detection(airline):
    cx1 = COAXIndex(airline.data)
    cx2 = COAXIndex(airline.data, groups=cx1.groups)
    fs = FullScan(airline.data)
    r = knn_rect_queries(airline.data, 3, 100, seed=9, sample_cap=5_000)[0]
    assert np.array_equal(cx2.query(r), fs.query(r))
