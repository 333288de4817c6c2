"""TPC-H Query 6 ("Forecasting Revenue Change", TPC-H Standard
Specification 3.0, Clause 2.4.6) as rects over ``tables/tpch.py``'s
``lineitem``.

Q6 admits the rows with ``l_shipdate >= DATE``, ``l_shipdate < DATE + 1
year``, ``l_discount between DISCOUNT - 0.01 and DISCOUNT + 0.01`` and
``l_quantity < QUANTITY``.  Its substitution parameters (Clause 2.4.6.3):
DATE is January 1 of a year in 1993..1997, DISCOUNT is 0.02..0.09 and
QUANTITY is 24 or 25, 80 combinations in all.  Each rect is one
combination, drawn uniformly; as half-open bounds on the table's integer
columns (dates in days since 1992-01-01, discounts in hundredths):

  l_shipdate  [DATE, DATE + 1 year)
  l_discount  [DISCOUNT - 1, DISCOUNT + 2)    the inclusive ``between``
  l_quantity  [column min, QUANTITY)

and every other column ``[column min, nextafter(column max))``, as the
kNN pool bounds a column.  About 1.9% of ``lineitem`` qualifies.

Parameters (the mix's ``pool``): ``size`` rects.
"""
from __future__ import annotations

import datetime
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from bench.tables.tpch import COLUMNS

EPOCH = datetime.date(1992, 1, 1)
YEARS = range(1993, 1998)
DISCOUNTS = range(2, 10)            # hundredths
QUANTITIES = (24, 25)


def _day(year: int) -> int:
    return (datetime.date(year, 1, 1) - EPOCH).days


def combinations() -> np.ndarray:
    """The 80 substitutions as ``(DATE, DATE + 1 year, DISCOUNT,
    QUANTITY)`` rows, dates in days."""
    return np.array([(_day(y), _day(y + 1), d, q) for y, d, q in
                     itertools.product(YEARS, DISCOUNTS, QUANTITIES)])


def make_pool(table, key, params: dict) -> np.ndarray:
    """``(size, D, 2)`` float64 rects (float32 values) from the device
    table ``(D, N)``."""
    size = int(params["size"])
    lo = np.asarray(jnp.min(table, axis=1)).astype(np.float64)
    hi = np.asarray(jnp.nextafter(jnp.max(table, axis=1),
                                  jnp.float32(jnp.inf))).astype(np.float64)
    combo = combinations()
    pick = combo[np.asarray(jax.random.randint(key, (size,), 0, len(combo)))]
    rects = np.repeat(np.stack([lo, hi], axis=1)[None], size, axis=0)
    rects[:, COLUMNS.index("l_shipdate")] = pick[:, 0:2]
    disc = COLUMNS.index("l_discount")
    rects[:, disc, 0] = pick[:, 2] - 1
    rects[:, disc, 1] = pick[:, 2] + 2
    rects[:, COLUMNS.index("l_quantity"), 1] = pick[:, 3]
    return rects
