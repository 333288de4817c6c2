"""OpenStreetMap US-Northeast nodes (COAX Table 1: 105M rows x 4), made on
the device.

The statistics are those of ``src/repro/data/synth.py:make_osm``, drawn
with ``jax.random`` in one jitted call that draws 4M rows at a time
(``seeding.columns_in_blocks``); cluster centres and import dates are
drawn once for the whole table:

  0 Id         U(0, 7e9)
  1 Timestamp  1.1e9 + 0.065 id + N(0, 3e6)            soft FD 0 -> 1
  2 Lat        one of 9 centres U(40, 47) + N(0, 0.35)
  3 Lon        the same centre's U(-80, -67) + N(0, 0.45)

Outliers (bulk imports): each row with probability ``outlier_frac`` has its
timestamp replaced by one of 12 import dates, 1.1e9 + U(0, 4.5e8), plus
N(0, 1e5).  ``make_osm`` sorts the ids along the rows and takes exactly
``outlier_frac * n`` outliers; row order carries no meaning for the index
or the queries, so the ids here are left unsorted, and the outlier count
is binomial around the same share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.seeding import columns_in_blocks

N_COLS = 4
N_CLUSTERS = 9
N_IMPORTS = 12
T0 = 1.1e9


def _block(key, rows: int, outlier_frac, dates, c_lat, c_lon):
    k = jax.random.split(key, 8)
    n = (rows,)
    f32 = jnp.float32
    ids = jax.random.uniform(k[0], n, f32, 0.0, 7e9)
    ts = T0 + ids * 0.065 + 3e6 * jax.random.normal(k[1], n, f32)
    out = jax.random.uniform(k[2], n, f32) < outlier_frac
    imported = (dates[jax.random.randint(k[3], n, 0, N_IMPORTS)]
                + 1e5 * jax.random.normal(k[4], n, f32))
    ts = jnp.where(out, imported, ts)
    which = jax.random.randint(k[5], n, 0, N_CLUSTERS)
    lat = c_lat[which] + 0.35 * jax.random.normal(k[6], n, f32)
    lon = c_lon[which] + 0.45 * jax.random.normal(k[7], n, f32)
    return jnp.stack([ids, ts, lat, lon])


@functools.partial(jax.jit, static_argnames=("n_rows",))
def make(key, n_rows: int, outlier_frac: float = 0.27):
    """(4, n_rows) float32 columns on the device."""
    key, kd, ka, ko = jax.random.split(key, 4)
    f32 = jnp.float32
    dates = T0 + jax.random.uniform(kd, (N_IMPORTS,), f32, 0.0, 4.5e8)
    c_lat = jax.random.uniform(ka, (N_CLUSTERS,), f32, 40.0, 47.0)
    c_lon = jax.random.uniform(ko, (N_CLUSTERS,), f32, -80.0, -67.0)
    return columns_in_blocks(
        key, n_rows, N_COLS,
        lambda k, rows: _block(k, rows, outlier_frac, dates, c_lat, c_lon))
