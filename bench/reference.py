"""The plain reference of a served answer, its control, and the comparison.

The reference answers a half-open rect by the definition: the ids of every
table row ``r`` with ``lo[j] <= r[j] < hi[j]`` on every column, by a full
scan on the device of the table the benchmark made.  It imports nothing
of the program and takes nothing the program made.  Rect bounds are
float32 values (``traffic/knn_rect.py``), so the float32 compare is exact.

The control is the same scan in bfloat16, the precision below the
configuration's float32: table and bounds rounded to bfloat16 before the
compare.  Put in the program's place it has to come out as not correct.

``compare`` decides ``correct``: an answer is wrong when its size differs
from the reference's count or, sizes equal, its sorted ids differ.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 8            # rects per scan of the table
BLOCK = 4096         # rows per block when the ids are fetched
MIN_CAP = 64         # smallest block buffer per rect (pow2 buckets above)


def _mask(table, lo, hi, dtype):
    t = table.astype(dtype)
    lo, hi = lo.astype(dtype), hi.astype(dtype)
    m = jnp.ones((lo.shape[0], t.shape[1]), bool)
    for j in range(t.shape[0]):
        m &= (t[j][None, :] >= lo[:, j:j + 1]) & (t[j][None, :] < hi[:, j:j + 1])
    return m


@functools.partial(jax.jit, static_argnames=("dtype",))
def _counts(table, lo, hi, dtype):
    return jnp.sum(_mask(table, lo, hi, dtype), axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("dtype", "cap"))
def _hit_blocks(table, lo, hi, dtype, cap):
    """The first ``cap`` blocks of ``BLOCK`` rows that hold a hit, per rect:
    their indices (``nb`` past the last) and their hit masks."""
    m = _mask(table, lo, hi, dtype)
    c, n = m.shape
    nb = -(-n // BLOCK)
    m = jnp.pad(m, ((0, 0), (0, nb * BLOCK - n))).reshape(c, nb, BLOCK)
    idx = jax.vmap(lambda h: jnp.nonzero(h, size=cap, fill_value=nb)[0])(
        m.any(axis=2))
    blocks = jnp.take_along_axis(m, jnp.minimum(idx, nb - 1)[:, :, None],
                                 axis=1)
    return idx, blocks & (idx < nb)[:, :, None]


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class FullScan:
    """Full-scan answers over the ``(D, N)`` float32 table, in ``dtype``."""

    def __init__(self, cols: np.ndarray, dtype=jnp.float32):
        self.table = jnp.asarray(cols, jnp.float32)
        self.dtype = dtype

    def _chunks(self, rects: np.ndarray):
        q = rects.shape[0]
        pad = -q % CHUNK
        lo = np.concatenate([rects[:, :, 0],
                             np.full((pad, rects.shape[1]), np.inf)])
        hi = np.concatenate([rects[:, :, 1],
                             np.full((pad, rects.shape[1]), -np.inf)])
        lo, hi = lo.astype(np.float32), hi.astype(np.float32)
        for s in range(0, q + pad, CHUNK):
            yield s, jnp.asarray(lo[s:s + CHUNK]), jnp.asarray(hi[s:s + CHUNK])

    def counts(self, rects: np.ndarray) -> np.ndarray:
        out = [np.asarray(_counts(self.table, lo, hi, self.dtype))
               for _, lo, hi in self._chunks(rects)]
        return np.concatenate(out)[:rects.shape[0]].astype(np.int64) \
            if out else np.zeros(0, np.int64)

    def ids(self, rects: np.ndarray, counts: np.ndarray) -> List[np.ndarray]:
        """Sorted ids of each rect; ``counts`` (from ``counts``) sizes the
        buffers."""
        out = []
        n_blocks = -(-self.table.shape[1] // BLOCK)
        for s, lo, hi in self._chunks(rects):
            c = counts[s:s + CHUNK]
            cap = min(_pow2(max(int(c.max(initial=0)), MIN_CAP)), n_blocks)
            idx, blocks = (np.asarray(a) for a in _hit_blocks(
                self.table, lo, hi, self.dtype, cap))
            for i in range(c.size):
                k, off = np.nonzero(blocks[i])
                out.append(idx[i, k].astype(np.int64) * BLOCK + off)
        return out

    def close(self) -> None:
        self.table.delete()


def compare(ref: FullScan, rects: np.ndarray, sizes: Sequence[int],
            answers_of: Callable[[np.ndarray], List[np.ndarray]]) -> dict:
    """Judge answers against ``ref``.  ``sizes[q]`` is the size of answer
    ``q``, or -1 when it never came; ``answers_of(qs)`` returns the sorted
    ids of those answers.  Only answers whose size matches the reference's
    count are fetched and compared id by id."""
    sizes = np.asarray(sizes, np.int64)
    want = ref.counts(rects)
    unanswered = int((sizes < 0).sum())
    wrong = int(((sizes >= 0) & (sizes != want)).sum())
    same = np.nonzero((sizes >= 0) & (sizes == want))[0]
    if same.size:
        got = answers_of(same)
        exp = ref.ids(rects[same], want[same])
        wrong += sum(not np.array_equal(g, e) for g, e in zip(got, exp))
    return {"compared": int(sizes.size), "wrong_answers": wrong,
            "unanswered": unanswered,
            "reference_rows": int(want.sum())}
