"""TPC-H ``lineitem`` (TPC-H Standard Specification 3.0, Clause 4.2.3),
made on the device.

The eight columns a Q6-style index holds, each an integer that float32
holds exactly, drawn with ``jax.random`` in one jitted call that draws 4M
rows at a time (``seeding.columns_in_blocks``):

  0 l_partkey        U[1, 2,000,000]                 (SF 10: SF x 200,000)
  1 l_quantity       U[1, 50]
  2 l_extendedprice  l_quantity x p_retailprice(l_partkey), in cents
  3 l_discount       U[0, 10] hundredths
  4 l_tax            U[0, 8] hundredths
  5 l_shipdate       o_orderdate + U[1, 121]
  6 l_commitdate     o_orderdate + U[30, 90]
  7 l_receiptdate    l_shipdate + U[1, 30]

``p_retailprice(k)`` is ``(90000 + ((k / 10) mod 20001) + 100 (k mod
1000)) / 100`` dollars (Clause 4.2.3, ``PART``), so the price in cents is
at most 50 x 209,900 = 10,495,000, below 2**24.  Dates are days since
1992-01-01; ``o_orderdate`` is U[1992-01-01, 1998-12-31 - 151 days] and is
shared by the 1 to 7 lines of one order, as dbgen shares it.  An order cut
by a block's end keeps one date for the lines the block holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.seeding import columns_in_blocks

COLUMNS = ("l_partkey", "l_quantity", "l_extendedprice", "l_discount",
           "l_tax", "l_shipdate", "l_commitdate", "l_receiptdate")
N_COLS = len(COLUMNS)
PARTS = 2_000_000           # SF 10
LAST_ORDERDATE = 2405       # 1998-12-31 (day 2556) - 151 days


def retail_cents(partkey):
    """``p_retailprice`` in cents, for integer part keys."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _block(key, rows: int):
    k = jax.random.split(key, 9)
    n = (rows,)
    i32 = jnp.int32
    # orders of 1..7 lines laid end to end; at most `rows` orders fill it
    lines = jax.random.randint(k[0], n, 1, 8, i32)
    order_of_row = jnp.searchsorted(jnp.cumsum(lines), jnp.arange(rows, dtype=i32),
                                    side="right")
    orderdate = jax.random.randint(k[1], n, 0, LAST_ORDERDATE + 1, i32)[
        order_of_row]
    partkey = jax.random.randint(k[2], n, 1, PARTS + 1, i32)
    quantity = jax.random.randint(k[3], n, 1, 51, i32)
    price = quantity * retail_cents(partkey)
    discount = jax.random.randint(k[4], n, 0, 11, i32)
    tax = jax.random.randint(k[5], n, 0, 9, i32)
    ship = orderdate + jax.random.randint(k[6], n, 1, 122, i32)
    commit = orderdate + jax.random.randint(k[7], n, 30, 91, i32)
    receipt = ship + jax.random.randint(k[8], n, 1, 31, i32)
    return jnp.stack([partkey, quantity, price, discount, tax, ship, commit,
                      receipt]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def make(key, n_rows: int):
    """(8, n_rows) float32 columns on the device."""
    return columns_in_blocks(key, n_rows, N_COLS, _block)
