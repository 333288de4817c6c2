"""Keys and generators drawn from ``--seed``, one stream per use.

JAX folds an integer seed into 32 bits, so seeds 2**33 + 7 and 7 would
give the same key; the seed goes through numpy's ``SeedSequence`` first,
which keeps every bit of any non-negative integer.
"""
from __future__ import annotations

import numpy as np

TABLE, POOL, ORDER, ARRIVALS, SAMPLE = range(5)    # stream ids
BLOCK_ROWS = 1 << 22    # rows a table generator draws at a time


def np_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def jax_key(seed: int, stream: int):
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def columns_in_blocks(key, n_rows: int, n_cols: int, block_fn,
                      block_rows: int = BLOCK_ROWS):
    """``(n_cols, n_rows)`` float32 made ``block_rows`` rows at a time by
    ``block_fn(key_i, rows) -> (n_cols, rows)``, so the generator's
    temporaries stay a block's size; call it inside ``jit``.  The last
    block ends at ``n_rows`` and overwrites the overlap."""
    import jax
    import jax.numpy as jnp

    b = min(block_rows, n_rows)

    def body(i, out):
        start = jnp.minimum(i * b, n_rows - b)
        return jax.lax.dynamic_update_slice_in_dim(
            out, block_fn(jax.random.fold_in(key, i), b), start, axis=1)

    return jax.lax.fori_loop(0, -(-n_rows // b), body,
                             jnp.zeros((n_cols, n_rows), jnp.float32))
