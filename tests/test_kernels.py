"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.kernels import bucket_histogram, range_scan_query, split_by_margin
from repro.kernels import ref
from repro.kernels.grid_histogram import grid_histogram
from repro.kernels.margin_split import margin_split
from repro.kernels.range_scan import range_scan


@pytest.mark.parametrize("n", [512, 1024, 4096])
@pytest.mark.parametrize("d", [2, 5, 8])
@pytest.mark.parametrize("tile", [256, 512])
def test_range_scan_shapes(n, d, tile):
    rng = np.random.default_rng(n + d)
    rows = rng.normal(0, 5, (d, n)).astype(np.float32)
    lo = np.full(d, -3, np.float32)
    hi = np.full(d, 3, np.float32)
    win = np.array([n // 8, n - n // 8], np.int32)
    mask_k, counts_k = range_scan(jnp.asarray(rows), jnp.asarray(lo),
                                  jnp.asarray(hi), jnp.asarray(win),
                                  tile=tile, interpret=True)
    mask_r, counts_r = ref.range_scan_ref(jnp.asarray(rows), jnp.asarray(lo),
                                          jnp.asarray(hi), jnp.asarray(win),
                                          tile=tile)
    assert np.array_equal(np.asarray(mask_k), np.asarray(mask_r))
    assert np.array_equal(np.asarray(counts_k), np.asarray(counts_r))


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(10, 2_000),
    d=st.integers(1, 6),
    seed=st.integers(0, 1_000),
)
def test_range_scan_query_property(n, d, seed):
    """Padded wrapper equals a brute-force numpy evaluation for ragged N."""
    rng = np.random.default_rng(seed)
    rows = rng.normal(0, 2, (d, n)).astype(np.float32)
    lo = rng.normal(-2, 1, d).astype(np.float32)
    hi = lo + rng.uniform(0.5, 4, d).astype(np.float32)
    count, mask = range_scan_query(rows, lo, hi, use_pallas=True)
    want = ((rows >= lo[:, None]) & (rows < hi[:, None])).all(axis=0)
    assert int(count) == int(want.sum())
    assert np.array_equal(np.asarray(mask, bool), want)


@pytest.mark.parametrize("buckets", [16, 64, 128])
@pytest.mark.parametrize("n", [999, 4096])
def test_grid_histogram_matches_ref(buckets, n):
    rng = np.random.default_rng(buckets + n)
    x = rng.normal(0, 3, n).astype(np.float32)
    d = rng.gamma(2.0, 2.0, n).astype(np.float32)
    h_k = bucket_histogram(x, d, buckets=buckets, use_pallas=True)
    h_r = bucket_histogram(x, d, buckets=buckets, use_pallas=False)
    assert_allclose(np.asarray(h_k), np.asarray(h_r), rtol=0, atol=0)
    assert float(h_k.sum()) == n  # every record lands in exactly one cell


def test_grid_histogram_agrees_with_numpy_bincount():
    rng = np.random.default_rng(7)
    n, b = 2_048, 32
    x = rng.uniform(0, 1, n).astype(np.float32)
    d = rng.uniform(0, 1, n).astype(np.float32)
    h = np.asarray(bucket_histogram(x, d, buckets=b, use_pallas=True))
    wx = (x.max() - x.min()) / b
    wd = (d.max() - d.min()) / b
    ix = np.clip(((x - x.min()) / wx).astype(int), 0, b - 1)
    jd = np.clip(((d - d.min()) / wd).astype(int), 0, b - 1)
    want = np.bincount(ix * b + jd, minlength=b * b).reshape(b, b)
    assert_allclose(h, want)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(8, 5_000),
    m=st.floats(-4, 4),
    b=st.floats(-50, 50),
    eps=st.floats(0.01, 10),
    seed=st.integers(0, 100),
)
def test_margin_split_property(n, m, b, eps, seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-100, 100, n).astype(np.float32)
    d = (m * x + b + rng.normal(0, eps, n)).astype(np.float32)
    disp_k, in_k = split_by_margin(x, d, m, b, eps, eps, use_pallas=True)
    disp_r, in_r = split_by_margin(x, d, m, b, eps, eps, use_pallas=False)
    assert_allclose(np.asarray(disp_k), np.asarray(disp_r), rtol=1e-6, atol=1e-4)
    assert np.array_equal(np.asarray(in_k), np.asarray(in_r))
    # oracle vs float64 numpy: agree away from the margin boundary (float32
    # rounding can flip rows whose displacement sits within the f32 ulp band)
    dispf = d.astype(np.float64) - (m * x.astype(np.float64) + b)
    want = np.abs(dispf) <= eps
    band = 1e-4 * (np.abs(m * x.astype(np.float64)) + abs(b) + eps + 1.0)
    near_edge = np.abs(np.abs(dispf) - eps) <= band
    got = np.asarray(in_k)
    assert ((got == want) | near_edge).all()


def test_margin_split_matches_alg1_split():
    """Kernel path reproduces the COAX build split exactly."""
    from repro.core import LinearModel
    rng = np.random.default_rng(11)
    x = rng.uniform(0, 1_000, 8_192).astype(np.float32)
    d = (2.0 * x + 5 + rng.normal(0, 3, 8_192)).astype(np.float32)
    model = LinearModel(m=2.0, b=5.0, eps_lb=6.0, eps_ub=6.0)
    want = model.inlier_mask(x.astype(np.float64), d.astype(np.float64))
    _, got = split_by_margin(x, d, 2.0, 5.0, 6.0, 6.0, use_pallas=True)
    assert (np.asarray(got) == want).mean() > 0.999
