"""Query translation (Eq. 2) unit + property tests."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import LinearModel, translate_dependent_interval, translate_rect
from repro.core.types import FDGroup, full_rect


def _model(m, b, lb, ub):
    return LinearModel(m=m, b=b, eps_lb=lb, eps_ub=ub)


def test_positive_slope_interval():
    m = _model(2.0, 10.0, 1.0, 1.0)
    lo, hi = translate_dependent_interval(m, 20.0, 30.0)
    # d >= 20 requires 2x + 10 + 1 >= 20 -> x >= 4.5
    # d <= 30 requires 2x + 10 - 1 <= 30 -> x <= 10.5
    assert abs(lo - 4.5) < 1e-12 and abs(hi - 10.5) < 1e-12


def test_negative_slope_interval_flips():
    m = _model(-2.0, 10.0, 1.0, 1.0)
    lo, hi = translate_dependent_interval(m, -30.0, -20.0)
    assert lo < hi


@settings(max_examples=200, deadline=None)
@given(
    slope=st.floats(-5, 5).filter(lambda v: abs(v) > 0.05),
    intercept=st.floats(-100, 100),
    eps_lb=st.floats(0.01, 10),
    eps_ub=st.floats(0.01, 10),
    x=st.floats(-1000, 1000),
    dlo=st.floats(-500, 500),
    width=st.floats(0.1, 200),
    disp_frac=st.floats(0, 1),
)
def test_property_inlier_matching_dep_constraint_is_in_window(
        slope, intercept, eps_lb, eps_ub, x, dlo, width, disp_frac):
    """Any inlier point whose dependent value satisfies [dlo, dhi) MUST fall
    inside the translated x-window (no false negatives — paper §4)."""
    model = _model(slope, intercept, eps_lb, eps_ub)
    dhi = dlo + width
    # construct an inlier at displacement in [-eps_lb, eps_ub]
    disp = -eps_lb + disp_frac * (eps_lb + eps_ub)
    d = slope * x + intercept + disp
    if not (dlo <= d < dhi):
        return  # point doesn't match the constraint; nothing to assert
    t_lo, t_hi = translate_dependent_interval(model, dlo, dhi)
    assert t_lo - 1e-6 <= x <= t_hi + 1e-6


def test_translate_rect_intersects_direct_and_derived():
    g = FDGroup(predictor=0, dependents=(1,), models={1: _model(1.0, 0.0, 1.0, 1.0)})
    rect = full_rect(3)
    rect[0] = [2.0, 50.0]     # direct constraint on predictor
    rect[1] = [10.0, 20.0]    # dependent constraint -> x in [9, 21]
    out = translate_rect(rect, [g], keep_dims=[0, 2])
    assert out.shape == (2, 2)
    assert abs(out[0, 0] - 9.0) < 1e-9   # max(2, 9)
    assert abs(out[0, 1] - 21.0) < 1e-9  # min(50, 21)
    assert np.isinf(out[1]).all()


def test_translate_rect_empty_intersection_clamps():
    g = FDGroup(predictor=0, dependents=(1,), models={1: _model(1.0, 0.0, 0.5, 0.5)})
    rect = full_rect(2)
    rect[0] = [100.0, 200.0]
    rect[1] = [0.0, 1.0]      # translated window [-0.5, 1.5] — disjoint
    out = translate_rect(rect, [g], keep_dims=[0])
    assert out[0, 0] >= out[0, 1] - 1e-9 or out[0, 1] <= 100.0  # empty window


def test_unconstrained_dependent_is_noop():
    g = FDGroup(predictor=0, dependents=(1,), models={1: _model(2.0, 0.0, 1.0, 1.0)})
    rect = full_rect(2)
    rect[0] = [5.0, 6.0]
    out = translate_rect(rect, [g], keep_dims=[0])
    assert out[0].tolist() == [5.0, 6.0]
