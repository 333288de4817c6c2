"""US airline on-time table (COAX Table 1: 80M rows x 8), made on the device.

The statistics are those of ``src/repro/data/synth.py:make_airline``, drawn
with ``jax.random`` instead of numpy's stream, in one jitted call that
draws 4M rows at a time (``seeding.columns_in_blocks``):

  0 Distance     gamma(2.2) * 420 + 80 miles
  1 TimeElapsed  28 + distance / 7.2 + N(0, 7)        soft FD 0 -> 1
  2 AirTime      elapsed - (18 + N(0, 3))             soft FD 0 -> 2
  3 DepTime      U(300, 1380) minutes of day
  4 ArrTime      dep + 0.97 elapsed + N(0, 9)         soft FD 3 -> 4
  5 SchedArr     arr - N(4, 6)                        soft FD 3 -> 5
  6 DayOfWeek    integer 0..6 + U(0, 0.01)
  7 Carrier      integer 0..13 + U(0, 0.01)

Outliers: each row independently with probability ``outlier_frac / 2``
gains a gamma(2) * 90 minute delay on TimeElapsed (after AirTime and
ArrTime were drawn), and with the same probability has its ArrTime
replaced by U(0, 1440) (after SchedArr was drawn).  ``make_airline`` takes
exactly ``outlier_frac * n`` rows split in half; here the count is
binomial around it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.seeding import columns_in_blocks

N_COLS = 8


def _block(key, rows: int, outlier_frac):
    k = jax.random.split(key, 13)
    n = (rows,)
    f32 = jnp.float32
    distance = jax.random.gamma(k[0], 2.2, n, f32) * 420.0 + 80.0
    elapsed = 28.0 + distance / 7.2 + 7.0 * jax.random.normal(k[1], n, f32)
    airtime = elapsed - (18.0 + 3.0 * jax.random.normal(k[2], n, f32))
    dep = jax.random.uniform(k[3], n, f32, 300.0, 1380.0)
    arr = dep + elapsed * 0.97 + 9.0 * jax.random.normal(k[4], n, f32)
    sched = arr - (4.0 + 6.0 * jax.random.normal(k[5], n, f32))
    day = (jax.random.randint(k[6], n, 0, 7).astype(f32)
           + jax.random.uniform(k[7], n, f32, 0.0, 0.01))
    carrier = (jax.random.randint(k[8], n, 0, 14).astype(f32)
               + jax.random.uniform(k[9], n, f32, 0.0, 0.01))
    u = jax.random.uniform(k[10], n, f32)
    half = outlier_frac / 2.0
    delayed = u < half                                 # big delays
    rewrapped = (u >= half) & (u < outlier_frac)       # red-eye wraps
    elapsed = jnp.where(delayed,
                        elapsed + jax.random.gamma(k[11], 2.0, n, f32) * 90.0,
                        elapsed)
    arr = jnp.where(rewrapped,
                    jax.random.uniform(k[12], n, f32, 0.0, 1440.0), arr)
    return jnp.stack([distance, elapsed, airtime, dep, arr, sched, day,
                      carrier])


@functools.partial(jax.jit, static_argnames=("n_rows",))
def make(key, n_rows: int, outlier_frac: float = 0.08):
    """(8, n_rows) float32 columns on the device."""
    return columns_in_blocks(
        key, n_rows, N_COLS, lambda k, rows: _block(k, rows, outlier_frac))
