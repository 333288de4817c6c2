"""TPC-H ``lineitem`` and its Q6 pool as the benchmark makes them, the
device plan's large answers on that table against a full scan, and the
readers of the large pass's spans."""
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from bench import run, seeding, spec
from bench.tests.cells import PEAKS, ROOT, SEED, tiny_cell

tpch = spec.load_module(ROOT, "tables", "tpch")
q6 = spec.load_module(ROOT, "traffic", "tpch_q6")
COL = {c: i for i, c in enumerate(tpch.COLUMNS)}


def _table(n_rows: int) -> np.ndarray:
    return np.asarray(tpch.make(seeding.jax_key(SEED, seeding.TABLE),
                                n_rows=n_rows))


def _pool(cols: np.ndarray, size: int) -> np.ndarray:
    return q6.make_pool(jax.numpy.asarray(cols),
                        seeding.jax_key(SEED, seeding.POOL), {"size": size})


def test_lineitem_keeps_the_clause_4_2_3_rules():
    f = _table(5000)
    t = f.astype(np.int64)
    assert (t == f).all()                        # integers, held exactly
    assert np.array_equal(_table(5000), f)       # the seed makes the table
    c = {k: t[i] for k, i in COL.items()}
    assert c["l_partkey"].min() >= 1 and c["l_partkey"].max() <= 2_000_000
    assert set(np.unique(c["l_quantity"])) == set(range(1, 51))
    assert np.array_equal(c["l_extendedprice"],
                          c["l_quantity"] * tpch.retail_cents(c["l_partkey"]))
    assert set(np.unique(c["l_discount"])) == set(range(0, 11))
    assert set(np.unique(c["l_tax"])) == set(range(0, 9))
    gap = c["l_receiptdate"] - c["l_shipdate"]
    assert gap.min() == 1 and gap.max() == 30
    commit = c["l_commitdate"] - c["l_shipdate"]
    assert commit.min() >= -91 and commit.max() <= 89
    assert c["l_shipdate"].min() >= 1
    assert c["l_shipdate"].max() <= tpch.LAST_ORDERDATE + 121
    # an order's 1..7 lines (4 on average) share its date, so about three
    # in four neighbouring rows commit within 60 days of each other
    step = np.abs(np.diff(c["l_commitdate"]))
    assert 0.7 < np.mean(step <= 60) < 0.8


def test_q6_pool_is_the_80_substitutions_as_half_open_rects():
    cols = _table(5000)
    rects = _pool(cols, 4000)
    assert rects.shape == (4000, tpch.N_COLS, 2)
    lo = cols.min(axis=1).astype(np.float64)
    hi = np.nextafter(cols.max(axis=1), np.float32(np.inf)).astype(np.float64)
    ship, disc, qty = COL["l_shipdate"], COL["l_discount"], COL["l_quantity"]
    rest = [i for i in range(tpch.N_COLS) if i not in (ship, disc)]
    assert np.array_equal(rects[:, rest, 0], np.broadcast_to(lo[rest],
                                                             (4000, 6)))
    other = [i for i in rest if i != qty]
    assert np.array_equal(rects[:, other, 1],
                          np.broadcast_to(hi[other], (4000, 5)))
    got = {(r[ship, 0], r[ship, 1], r[disc, 0], r[disc, 1], r[qty, 1])
           for r in rects}
    years = [(366, 731), (731, 1096), (1096, 1461), (1461, 1827),
             (1827, 2192)]                     # Jan 1 1993 .. Jan 1 1998
    want = {(a, b, d - 1, d + 2, q) for a, b in years
            for d in range(2, 10) for q in (24, 25)}
    assert got == want


def _q6_scan(cols: np.ndarray, rects: np.ndarray):
    """Sorted ids of each rect by a plain numpy scan of the table."""
    rows = cols.T
    return [np.nonzero(np.all((rows >= r[:, 0]) & (rows < r[:, 1]),
                              axis=1))[0] for r in rects]


@pytest.mark.parametrize("route", [{}, {"use_pallas": True,
                                       "interpret": True}],
                         ids=["oracle", "pallas"])
def test_large_answers_equal_a_full_scan(route):
    """Every Q6 answer on 50k rows is past a ``hit_cap`` of 64: the device
    plan serves each from its large pass, id for id as a full scan of the
    table finds them, and answers none again on the host."""
    from repro.core import COAXIndex
    from repro.engine import BatchQueryExecutor

    cols = _table(50_000)
    rects = _pool(cols, 8)
    want = _q6_scan(cols, rects)
    assert min(w.size for w in want) > 64
    idx = COAXIndex(np.ascontiguousarray(cols.T),
                    device_opts={"hit_cap": 64, **route})
    ex = BatchQueryExecutor(idx, max_batch=3, backend="device")
    got = ex.execute(rects)
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    s = ex.stats()
    assert s["hit_overflows"] == 0 and s["device_fallbacks"] == 0
    assert s["large_results"] == rects.shape[0]


def _span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "args": args}


def test_large_pass_readers_on_synthetic_spans():
    ctx = NS(served={"queries": 8}, spans=[
        _span("device.large", 1.0, 1.004, queries=3, hits=3_300_000),
        _span("device.large", 2.0, 2.006, queries=2, hits=2_200_000),
        _span("device.assemble", 1.004, 1.005),
        _span("device.assemble", 2.006, 2.009),
        _span("device.copy", 0.5, 0.6)])
    read = {n: spec.load_module(ROOT, "metrics", n).read
            for n in ("large_pass_ms", "assemble_ms", "large_result_pct")}
    assert read["large_pass_ms"](ctx) == pytest.approx(5.0)
    assert read["assemble_ms"](ctx) == pytest.approx(2.0)
    assert read["large_result_pct"](ctx) == pytest.approx(62.5)
    # a program without the large pass reads nothing
    none = NS(served={"queries": 8}, spans=[_span("device.copy", 0, 1)])
    assert all(r(none) is None for r in read.values())


def test_traced_q6_cell_reports_the_large_pass():
    """The cell cut to 80k rows, where Q6 answers hold about 1,500 rows,
    past ``hit_cap``: a traced run is correct and reads the new metrics."""
    cell = tiny_cell("tpch-sf10.q6-closed", n_rows=80_000, pool=16,
                     outstanding=3)
    out = run.run_cell(cell, SEED, 0.5, True, PEAKS, jax.devices(),
                       log=lambda **kv: None, kernel_check=False)
    assert out["correct"]
    m = out["metrics"]
    assert m["large_result_pct.qps"]["value"] == 100.0
    assert m["reanswer_pct.qps"]["value"] == 0.0
    assert m["large_pass_ms.qps"]["value"] > 0
    assert m["assemble_ms.qps"]["value"] > 0
