"""Small copies of the benchmark's cells for CPU tests."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**33 + 12345          # wider than 32 bits, as real seeds can be


def tiny_cell(workload: str, n_rows: int = 6000, pool: int = 64,
              rate_qps: float = 100.0, outstanding: int = 64) -> spec.Cell:
    """The cell as ``BENCHMARK.json`` has it, cut to a size a test holds."""
    cell = spec.load_cell(workload)
    cell.config = dict(cell.config, n_rows=n_rows)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["pool"]["size"] = pool
    arr = cell.traffic["arrivals"]
    if "rate_qps" in arr:
        arr["rate_qps"] = rate_qps
    if "outstanding" in arr:
        arr["outstanding"] = outstanding
    return cell


def table_and_pool(table: str, n_rows: int, size: int, seed: int = SEED):
    """A device table ``(D, N)`` and its kNN-rect pool, as a run makes
    them."""
    import numpy as np

    from bench import seeding

    cols = spec.load_module(ROOT, "tables", table).make(
        seeding.jax_key(seed, seeding.TABLE), n_rows=n_rows)
    rects = spec.load_module(ROOT, "traffic", "knn_rect").make_pool(
        cols, seeding.jax_key(seed, seeding.POOL), {"k": 10, "size": size})
    return np.asarray(cols), rects
