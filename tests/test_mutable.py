"""Mutable index lifecycle (DESIGN.md §5): delta plane, tombstones,
incremental FD maintenance, compaction.

The contract under test: after ANY interleaving of inserts/deletes (with
and without a compaction), ``query``, ``query_batch`` (numpy) and
``query_batch`` (device) return bit-identical hit sets equal to a
scratch-built ``COAXIndex`` over the final row set — and to the
``FullScan`` ground truth — across workloads that include FD-violating
inserts.  Plus the lifecycle plumbing: compaction triggers (size + §7.2
drift), epoch versioning through the engine, server write admission with
per-wave snapshot semantics, ``cancel``, and footprint accounting.
"""
import copy

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core import COAXIndex, CoaxConfig, DeltaPlane, FullScan, full_rect
from repro.data import make_airline, make_generic_fd
from repro.engine import BatchQueryExecutor, QueryServer
from workloads import (NOAUTO, assert_equiv, fullscan_expected,
                       mutable_workloads, rects_for, violate_fd)


def _rects(data, n=10, seed=0):
    """Mutation-schedule rect mix (no far-out-of-range extreme rect)."""
    return rects_for(data, n=n, seed=seed, extremes=False, sample_cap=8_000)


@pytest.mark.parametrize("name,ds,more", mutable_workloads(),
                         ids=lambda w: w if isinstance(w, str) else "")
def test_interleaved_ops_equal_scratch_rebuild(name, ds, more):
    """Deterministic interleaving: base deletes, in-pattern inserts,
    FD-VIOLATING inserts, delta-log deletes, a compaction, then more writes —
    equivalence checked before AND after the compaction, numpy and device."""
    rng = np.random.default_rng(1)
    idx = COAXIndex(ds.data, NOAUTO)
    rects = _rects(ds.data, n=8, seed=0)

    idx.delete(rng.choice(ds.data.shape[0], 400, replace=False))
    fresh = more(101, 600)
    ids_a = idx.insert(fresh[:300])                      # in-pattern
    ids_b = idx.insert(violate_fd(ds, fresh[300:]))        # FD-violating
    assert idx.delta_outlier.n_live > 0, "violators must hit the outlier delta"
    idx.delete(ids_a[:50])                               # delta-log tombstones
    idx.delete(ids_b[:50])
    assert idx.delete(ids_a[:50]) == 0                   # double delete: no-op
    assert_equiv(idx, rects, device=(name == "airline"), tag=f"{name}-pre")

    info = idx.compact()
    assert info["epoch"] == idx.epoch == 1
    assert idx.delta_rows == 0 and idx.tombstone_count == 0
    assert idx.primary.epoch == idx.outlier.epoch == 1

    idx.delete(np.concatenate([ids_a[50:80], ids_b[50:80]]))
    idx.insert(violate_fd(ds, more(103, 120)))
    assert_equiv(idx, rects, device=True, tag=f"{name}-post")


def test_row_count_and_id_bookkeeping():
    ds = make_generic_fd(4_000, 4, ((0, 1),), seed=2)
    idx = COAXIndex(ds.data, NOAUTO)
    assert idx.n_rows == 4_000
    ids = idx.insert(ds.data[:70])
    assert ids.tolist() == list(range(4_000, 4_070))
    assert idx.n_rows == 4_070
    assert idx.delete(ids[:20]) == 20
    assert idx.delete([4_000_000]) == 0                  # unknown id ignored
    assert idx.n_rows == 4_050
    idx.compact()
    assert idx.n_rows == 4_050 == idx.data.shape[0]
    # ids survive compaction; the next insert continues the id sequence
    new = idx.insert(ds.data[:1])
    assert int(new[0]) == 4_070


def test_empty_index_after_deleting_everything():
    ds = make_generic_fd(2_000, 4, ((0, 1),), seed=4)
    idx = COAXIndex(ds.data, NOAUTO)
    assert idx.delete(np.arange(2_000)) == 2_000
    rects = _rects(ds.data, n=4, seed=1)
    for r in rects:
        assert idx.query(r).size == 0
    qids, rids = idx.query_batch(rects)
    assert qids.size == 0 and rids.size == 0
    idx.compact()
    assert idx.n_rows == 0 and idx.query(full_rect(4)).size == 0
    ids = idx.insert(ds.data[:10])                       # rebuild from empty
    assert np.array_equal(np.sort(idx.query(full_rect(4))), ids)


# --------------------------------------------------------------------- #
# Property test: arbitrary interleavings == scratch rebuild (satellite)
# --------------------------------------------------------------------- #
_PROP_DS = make_generic_fd(1_500, 4, ((0, 1),), seed=5)
_PROP_BASE = COAXIndex(_PROP_DS.data, NOAUTO)
_PROP_POOL = make_generic_fd(2_048, 4, ((0, 1),), seed=6).data
_PROP_RECTS = _rects(_PROP_DS.data, n=6, seed=9)

_op = st.one_of(
    st.tuples(st.just("insert"), st.integers(0, 1_900), st.integers(1, 64),
              st.booleans()),
    st.tuples(st.just("delete"), st.integers(0, 10_000), st.integers(1, 64)),
    st.tuples(st.just("compact")),
)


@settings(max_examples=12, deadline=None)
@given(st.lists(_op, min_size=1, max_size=8))
def test_property_any_interleaving_equals_scratch(ops):
    idx = copy.deepcopy(_PROP_BASE)
    for op in ops:
        if op[0] == "insert":
            _, start, m, violate = op
            rows = _PROP_POOL[start:start + m]
            if violate:
                rows = violate_fd(_PROP_DS, rows)
            idx.insert(rows)
        elif op[0] == "delete":
            _, seed, m = op
            rng = np.random.default_rng(seed)
            live = idx.live_rows()[1]
            if live.size:
                idx.delete(rng.choice(live, min(m, live.size), replace=False))
        else:
            idx.compact()
    assert_equiv(idx, _PROP_RECTS, scratch=True, tag=str(ops)[:80])


# --------------------------------------------------------------------- #
# Compaction triggers
# --------------------------------------------------------------------- #
def test_size_trigger_auto_compacts():
    ds = make_generic_fd(4_000, 4, ((0, 1),), seed=2)
    cfg = CoaxConfig(auto_compact=True, compact_min_delta=128,
                     compact_delta_frac=0.01, drift_min_delta=10**9)
    idx = COAXIndex(ds.data, cfg)
    idx.insert(ds.data[:100])                 # below both thresholds
    assert idx.compactions == 0 and idx.delta_rows == 100
    idx.insert(ds.data[:100])                 # load 200 >= max(128, 40)
    assert idx.compactions == 1 and idx.epoch == 1
    assert idx.delta_rows == 0 and idx.n_rows == 4_200


def test_drift_trigger_relearns_on_fd_break():
    """A burst of inserts following a DIFFERENT linear trend drags the live
    posterior slope away from the frozen model; the §7.2 predictability
    ratio falls below the threshold and compaction fires with a relearn."""
    ds = make_generic_fd(4_000, 4, ((0, 1),), seed=3)
    cfg = CoaxConfig(auto_compact=True, compact_min_delta=10**9,
                     compact_delta_frac=10.0, drift_min_delta=64,
                     drift_threshold=0.5)
    idx = COAXIndex(ds.data, cfg)
    assert idx.drift_predictability() > 0.9   # seeded at the frozen trend
    drifted = violate_fd(ds, make_generic_fd(3_000, 4, ((0, 1),), seed=8).data)
    idx.insert(drifted)
    assert idx.compactions == 1 and idx.epoch == 1, \
        "drift trigger should have compacted"
    # after relearn the trackers are reseeded from the merged snapshot
    assert idx.drift_predictability() > idx.config.drift_threshold


def test_delta_plane_unit():
    dp = DeltaPlane(2)
    dp.insert(np.array([[0.0, 0.0], [5.0, 5.0]], np.float32), np.array([10, 11]))
    assert len(dp) == 2 and dp.n_tombstones == 0
    absorbed = dp.tombstone_log(np.array([11, 99]))
    assert absorbed.tolist() == [True, False] and dp.n_live == 1
    assert dp.tombstone_base(np.array([3, 3, 4])) == 2    # dupes collapse
    assert dp.is_dead(np.array([3, 4, 10, 11])).tolist() == [True, True, False, True]
    rect = np.array([[-1.0, 1.0], [-1.0, 1.0]])
    assert dp.scan(rect).tolist() == [10]
    qids, rids = dp.scan_batch(np.stack([rect, full_rect(2)]))
    assert qids.tolist() == [0, 1] and rids.tolist() == [10, 10]
    assert dp.nbytes() == 2 * 2 * 4 + 2 * 8 + 3 * 8
    # compaction feed excludes tombstoned log rows
    rows, ids = dp.live_log()
    assert ids.tolist() == [10] and rows.shape == (1, 2)


# --------------------------------------------------------------------- #
# Satellites: footprint accounting, executor revalidation, server writes
# --------------------------------------------------------------------- #
def test_memory_footprint_includes_bbox_and_delta():
    ds = make_generic_fd(6_000, 5, ((0, 1), (2, 3)), seed=7)
    idx = COAXIndex(ds.data, NOAUTO)
    assert idx._outlier_lo is not None
    base = idx.memory_footprint()
    grids = idx.primary.memory_footprint() + idx.outlier.memory_footprint()
    bbox = idx._outlier_lo.nbytes + idx._outlier_hi.nbytes
    assert base >= grids + bbox               # bbox arrays are accounted
    ids = idx.insert(ds.data[:200])
    idx.delete(ids[:40])
    idx.delete(np.arange(40))
    grown = idx.memory_footprint()
    delta_bytes = idx.delta_primary.nbytes() + idx.delta_outlier.nbytes()
    assert delta_bytes > 0 and grown == base + delta_bytes
    d = idx.describe()
    assert d["outlier_bbox_bytes"] == bbox
    assert d["delta_primary"]["bytes"] + d["delta_outlier"]["bytes"] == delta_bytes
    assert d["tombstones"] == 80 and d["n_rows"] == 6_000 + 200 - 80


def test_executor_revalidates_backend_and_tracks_epochs():
    jax = pytest.importorskip("jax")
    ds = make_airline(6_000, seed=2)
    idx = COAXIndex(ds.data, NOAUTO)
    rects = _rects(ds.data, n=6, seed=3)
    ex = BatchQueryExecutor(idx, max_batch=4, backend="device")
    got = ex.execute(rects)
    idx.backend = "numpy"                     # external flip mid-stream...
    idx.insert(make_airline(64, seed=9).data)
    idx.compact()                             # ...and a compaction (epoch 1)
    got2 = ex.execute(rects)
    assert idx.backend == "device", "executor must re-assert its backend"
    s = ex.stats()
    assert s["backend"] == "device"
    assert s["epochs"] == [0, 1]              # waves stamped with their epoch
    want = fullscan_expected(*idx.live_rows(), rects)
    for i in range(rects.shape[0]):
        assert np.array_equal(got2[i], want[i]), i
    assert ex.wave_stats[-1].epoch == 1


def test_server_write_admission_and_cancel():
    ds = make_generic_fd(5_000, 4, ((0, 1),), seed=1)
    idx = COAXIndex(ds.data, NOAUTO)
    srv = QueryServer(idx, max_batch=4)
    rects = _rects(ds.data, n=5, seed=2)
    qids = srv.submit_many(rects)
    assert srv.cancel(qids[0]) and not srv.cancel(qids[0])
    assert not srv.cancel(10**6)
    w1 = srv.insert(ds.data[:80])
    w2 = srv.delete(np.arange(30))
    assert srv.stats()["writes_pending"] == 2
    res = srv.drain()
    assert qids[0] not in res and len(res) == len(rects) - 1
    assert srv.write_results[w1].size == 80 and srv.write_results[w2] == 30
    # per-wave snapshot: writes were applied before wave 1, so every answer
    # reflects them
    want = fullscan_expected(*idx.live_rows(), rects)
    for qid, w in zip(qids[1:], want[1:]):
        assert np.array_equal(res[qid], w)
    s = srv.stats()
    assert s["writes_applied"] == 2 and s["writes_pending"] == 0
    assert s["rows_inserted"] == 80 and s["rows_deleted"] == 30
    assert s["delta_rows"] == 80 and s["tombstones"] == 30
    # a drain with only writes queued still applies them
    srv.insert(ds.data[:5])
    srv.drain()
    assert srv.stats()["writes_applied"] == 3


def test_server_rejects_writes_on_immutable_engine():
    ds = make_generic_fd(1_000, 4, ((0, 1),), seed=1)
    srv = QueryServer(FullScan(ds.data))
    with pytest.raises(TypeError):
        srv.insert(ds.data[:2])
    with pytest.raises(TypeError):
        srv.delete([0])
