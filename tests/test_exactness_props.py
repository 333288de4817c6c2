"""Property tests for the exactness primitives (DESIGN.md §4, §6).

Every backend's bit-identity argument bottoms out in two facts:

* paired f32 rounding is conservative — ``f32_ceil(c)`` is the smallest
  float32 >= the float64 bound ``c`` (and ``f32_floor`` its mirror), so a
  float32 record can be compared against ``c`` entirely in float32 without
  ever flipping a membership decision;
* the batched Eq. 2 translation is BIT-identical to the scalar reference,
  so the numpy, device and sharded planes all navigate from the same
  nav-rects.

Hypothesis drives both over adversarial floats — ±inf, subnormals,
f32-overflowing magnitudes — alongside deterministic spot checks of the
same corners.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core import COAXIndex, translate_rect, translate_rects
from repro.core.gridfile import f32_ceil
from repro.data import make_generic_fd
from repro.engine.device import f32_floor

# NaN-free float64s, infinities and subnormals included
_f64 = st.floats(allow_nan=False, allow_infinity=True, width=64)
# float32 record values (what the stored rows can actually hold)
_f32 = st.floats(allow_nan=False, allow_infinity=True, width=32)

_ADVERSARIAL = [
    0.0, -0.0, np.inf, -np.inf, 1e39, -1e39,           # beyond f32 range
    float(np.finfo(np.float32).max), float(np.finfo(np.float32).tiny),
    5e-324, -5e-324,                                    # f64 subnormals
    float(np.float64(np.float32(1.1)) + 1e-12),         # straddles an f32
    1.0 + 2**-40, -1.0 - 2**-40,
]


def _probe_values(c):
    """float32 values worth testing against a float64 bound ``c``: the
    rounded bound itself and its f32 neighbours."""
    with np.errstate(over="ignore"):
        y = np.float64(np.clip(c, -3.4e38, 3.4e38)).astype(np.float32)
    return [np.float32(y),
            np.nextafter(y, np.float32(-np.inf)),
            np.nextafter(y, np.float32(np.inf))]


def _check_ceil(c, vs):
    cu = f32_ceil(np.asarray([c]))[0]
    assert cu.dtype == np.float32
    cu64 = float(cu)
    assert cu64 >= c                                     # conservative
    if np.isfinite(cu64) and cu64 > float(np.finfo(np.float32).min):
        # minimal: the next f32 down is strictly below c
        assert float(np.nextafter(np.float32(cu), np.float32(-np.inf))) < c
    for v in vs:                                         # membership-preserving
        v64 = float(np.float32(v))
        assert (v64 >= c) == (np.float32(v) >= cu), (c, v)
        assert (v64 < c) == (np.float32(v) < cu), (c, v)


def _check_floor(c, vs):
    fl = f32_floor(np.asarray([c]))[0]
    assert fl.dtype == np.float32
    fl64 = float(fl)
    assert fl64 <= c                                     # conservative
    if np.isfinite(fl64) and fl64 < float(np.finfo(np.float32).max):
        assert float(np.nextafter(np.float32(fl), np.float32(np.inf))) > c
    for v in vs:
        v64 = float(np.float32(v))
        assert (v64 <= c) == (np.float32(v) <= fl), (c, v)
        assert (v64 > c) == (np.float32(v) > fl), (c, v)


def test_f32_rounding_spot_checks():
    """The adversarial corner list runs even without hypothesis."""
    for c in _ADVERSARIAL:
        _check_ceil(c, _probe_values(c))
        _check_floor(c, _probe_values(c))


@settings(max_examples=300, deadline=None)
@given(c=_f64, v=_f32)
def test_f32_ceil_paired_rounding_conservative(c, v):
    _check_ceil(c, [np.float32(v)] + _probe_values(c))


@settings(max_examples=300, deadline=None)
@given(c=_f64, v=_f32)
def test_f32_floor_paired_rounding_conservative(c, v):
    _check_floor(c, [np.float32(v)] + _probe_values(c))


@settings(max_examples=200, deadline=None)
@given(c=_f64)
def test_f32_floor_ceil_bracket(c):
    """floor(c) <= c <= ceil(c), and they coincide exactly when c is
    representable in float32."""
    fl = float(f32_floor(np.asarray([c]))[0])
    cu = float(f32_ceil(np.asarray([c]))[0])
    assert fl <= c <= cu
    representable = float(np.float32(c)) == c or not np.isfinite(c)
    assert (fl == cu) == representable


# --------------------------------------------------------------------- #
# Batched vs scalar Eq. 2 translation
# --------------------------------------------------------------------- #
_TR_DS = make_generic_fd(6_000, 5, ((0, 1), (2, 3)), seed=7)
_TR_IDX = COAXIndex(_TR_DS.data)

_bound = st.one_of(_f64, st.sampled_from(_ADVERSARIAL))


def _check_translate_agreement(rects):
    batch = translate_rects(rects, _TR_IDX.groups, _TR_IDX.keep_dims)
    for i, r in enumerate(rects):
        single = translate_rect(r, _TR_IDX.groups, _TR_IDX.keep_dims)
        assert np.array_equal(batch[i], single), (i, r.tolist())


def test_translate_degenerate_inf_constraints():
    """Deterministic corners: fully unconstrained, half-open, and the
    degenerate all-infinite dependent constraints the scalar path skips."""
    d = _TR_DS.data.shape[1]
    dep = _TR_IDX.groups[0].dependents[0]
    base = np.stack([np.full(d, -np.inf), np.full(d, np.inf)], axis=-1)
    rects = []
    for lo, hi in [(-np.inf, np.inf), (np.inf, np.inf), (-np.inf, -np.inf),
                   (1e39, 1e39), (-np.inf, 0.0), (0.0, np.inf)]:
        r = base.copy()
        r[dep] = [lo, hi]
        rects.append(r)
    _check_translate_agreement(np.stack(rects))


@settings(max_examples=150, deadline=None)
@given(st.lists(st.tuples(_bound, _bound), min_size=5, max_size=5))
def test_translate_rects_matches_scalar_on_adversarial_floats(bounds):
    rect = np.array([[min(a, b), max(a, b)] for a, b in bounds])
    _check_translate_agreement(rect[None])
    # and inside a batch whose other rows are ordinary
    other = np.stack([np.zeros(5), np.ones(5)], axis=-1)
    _check_translate_agreement(np.stack([other, rect, other]))


def test_hypothesis_is_driving():
    """Guard: the @given tests above must be real property tests, not
    silently inert decorators."""
    from hypothesis import given as real_given
    assert given is real_given
    assert hasattr(
        test_translate_rects_matches_scalar_on_adversarial_floats,
        "hypothesis")
