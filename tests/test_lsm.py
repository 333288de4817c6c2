"""LSM write path: tiered delta runs + background compaction with handoff.

Three layers of coverage for DESIGN.md §5.3–§5.4:

* ``DeltaPlane`` tiered runs — spill/merge policy invariants, the
  binary-searched ``scan_batch`` against a dense oracle (tombstones
  included), sub-linear probe accounting, and the ``organized``-boundary
  state round-trip (L0 fill level is part of §7.3 determinism);
* the epoch-handoff window — queries, inserts and deletes interleaved
  while a background build is deterministically HELD OPEN (the poll is
  stubbed to a no-op, so the old epoch must keep serving exactly), on
  numpy and device backends, single and sharded, plus a hypothesis
  variant over drawn op sequences;
* crash injection mid-handoff — the primary dies inside
  ``Durability.handoff_rotate`` after the tail re-journal but before the
  new snapshot publishes; recovery must replay the old pair, re-fire the
  compaction synchronously and land bit-identical to a never-crashed
  synchronous twin (§7.5).

Satellites asserted here: amortized ``trigger_checks``, the
``describe()`` background/run surfacing, and the device plane's
``compile_count`` staying flat across compaction epochs (pow2-bucketed
images + ``_PlanBase.adopt``).
"""
import numpy as np
import pytest

from repro.core import COAXIndex, CoaxConfig
from repro.core.delta import DeltaPlane
from repro.core.types import rect_contains
from repro.data import make_generic_fd
from repro.engine import ShardedCOAX
from repro.storage import Durability, restore

from hypothesis import given, settings, strategies as st
from workloads import assert_equiv, rects_for, violate_fd

_DS = make_generic_fd(9_000, 5, ((0, 1), (2, 3)), seed=7)


def _more(seed, m):
    return make_generic_fd(m, 5, ((0, 1), (2, 3)), seed=seed).data


# triggers low enough that short schedules cross them; checks amortized
BG = CoaxConfig(compact_min_delta=300, compact_delta_frac=0.01,
                drift_min_delta=200, compact_check_rows=64,
                delta_l0_spill=64, background_compact=True)
SYNC = CoaxConfig(compact_min_delta=300, compact_delta_frac=0.01,
                  drift_min_delta=200, compact_check_rows=64,
                  delta_l0_spill=64, background_compact=False)


def _hold_window_open(idx):
    """Freeze the handoff window: shadow ``poll_handoff`` with a no-op so
    the finished build cannot install and every query/write must be served
    by the old epoch ∪ its delta — the §5.4 during-build contract, made
    deterministic."""
    idx.poll_handoff = lambda wait=False: False


def _release_window(idx):
    del idx.poll_handoff               # uncover the real method


# --------------------------------------------------------------------- #
# DeltaPlane: tiered runs
# --------------------------------------------------------------------- #
def test_tiered_spill_and_merge_policy():
    dp = DeltaPlane(3, key_dim=1, l0_spill=8)
    rng = np.random.default_rng(0)
    next_id = 0
    for i in range(40):
        rows = rng.random((8, 3)).astype(np.float32)
        spilled = dp.insert(rows, np.arange(next_id, next_id + 8))
        next_id += 8
        assert spilled == 1                      # exactly at the fill level
        assert dp.l0_rows == 0
        sizes = [p.size for p, _ in dp._runs]
        assert sum(sizes) == dp._organized == dp.n_log
        # tier invariant: after merging, every older run is > 2x its newer
        # neighbour, so the run count stays logarithmic
        for a, b in zip(sizes, sizes[1:]):
            assert a > 2 * b, sizes
        for pos, keys in dp._runs:               # runs are sorted views
            assert np.all(np.diff(keys) >= 0)
            assert np.array_equal(
                np.sort(keys),
                np.sort(dp._log_rows()[pos, 1].astype(np.float64)))
    assert dp.spills == 40
    assert dp.merges > 0
    assert dp.n_runs <= int(np.log2(dp.n_log)) + 1
    # sub-spill appends stay in L0
    dp.insert(rng.random((3, 3)).astype(np.float32), np.arange(10**6, 10**6 + 3))
    assert dp.l0_rows == 3 and dp.spills == 40


def _dense_oracle(dp, rects):
    rows, ids = dp.live_log()
    q_parts, r_parts = [], []
    for qi, rect in enumerate(rects):
        hit = ids[rect_contains(np.asarray(rect, np.float64), rows)]
        q_parts.append(np.full(hit.size, qi, np.int64))
        r_parts.append(hit)
    q = np.concatenate(q_parts) if q_parts else np.empty(0, np.int64)
    r = np.concatenate(r_parts) if r_parts else np.empty(0, np.int64)
    order = np.lexsort((r, q))
    return q[order], r[order]


def test_scan_batch_equals_dense_oracle_with_tombstones():
    rng = np.random.default_rng(1)
    dp = DeltaPlane(4, key_dim=2, l0_spill=32)
    for i in range(30):
        m = int(rng.integers(5, 60))
        rows = rng.random((m, 4)).astype(np.float32)
        dp.insert(rows, np.arange(dp.n_log, dp.n_log + m))
        if i % 4 == 3:
            dp.tombstone_log(rng.integers(0, dp.n_log, 15).astype(np.int64))
    # rect mix: narrow key-dim windows, an empty window, full range, ±inf
    rects = []
    for _ in range(12):
        lo = rng.random(4) * 0.9
        hi = lo + rng.random(4) * 0.15
        rects.append(np.stack([lo, hi], axis=-1))
    rects.append(np.stack([np.full(4, 2.0), np.full(4, 3.0)], axis=-1))
    rects.append(np.stack([np.full(4, -np.inf), np.full(4, np.inf)], axis=-1))
    rects = np.stack(rects)
    q, r = dp.scan_batch(rects)
    order = np.lexsort((r, q))
    oq, orr = _dense_oracle(dp, rects)
    assert np.array_equal(q[order], oq) and np.array_equal(r[order], orr)
    # per-run binary search means narrow windows probe far fewer candidate
    # rows than a dense scan of the whole log would
    assert dp.last_scan_probed < rects.shape[0] * dp.n_live / 2
    # scalar scan agrees per rect
    for qi, rect in enumerate(rects):
        assert np.array_equal(np.sort(dp.scan(rect)), orr[oq == qi])


def test_state_roundtrip_preserves_l0_boundary():
    rng = np.random.default_rng(2)
    dp = DeltaPlane(3, key_dim=1, l0_spill=16)
    dp.insert(rng.random((32, 3)).astype(np.float32), np.arange(32))
    dp.insert(rng.random((8, 3)).astype(np.float32), np.arange(32, 40))
    dp.tombstone_log(np.array([3, 17, 35]))
    dp.tombstone_base(np.array([10**7]))
    assert dp.l0_rows == 8
    rt = DeltaPlane.from_state(3, dp.state_dict(), key_dim=1, l0_spill=16)
    assert rt._organized == dp._organized == 32
    assert rt.l0_rows == dp.l0_rows and rt.n_runs == 1
    assert rt.n_log_dead == dp.n_log_dead
    assert rt.n_base_dead == dp.n_base_dead
    rects = np.stack([np.stack([np.full(3, 0.2), np.full(3, 0.8)], axis=-1)])
    for plane in (dp, rt):
        q, r = plane.scan_batch(rects)
        o = np.lexsort((r, q))
        plane.hits = (q[o], r[o])
    assert np.array_equal(dp.hits[0], rt.hits[0])
    assert np.array_equal(dp.hits[1], rt.hits[1])
    # the restored L0 fill level spills at the SAME append as the original
    more = rng.random((8, 3)).astype(np.float32)
    assert dp.insert(more, np.arange(100, 108)) == \
        rt.insert(more, np.arange(100, 108)) == 1


# --------------------------------------------------------------------- #
# Amortized trigger checks
# --------------------------------------------------------------------- #
def test_trigger_checks_amortized_by_rows():
    cfg = CoaxConfig(compact_check_rows=64, compact_min_delta=10**9,
                     drift_min_delta=10**9)
    idx = COAXIndex(_DS.data[:2_000], cfg)
    for i in range(200):                       # one-row writes, 200 of them
        idx.insert(_DS.data[i % 2_000][None])
    assert idx.trigger_checks == 200 // 64     # not 200
    assert idx.describe()["trigger_checks"] == idx.trigger_checks


def test_trigger_check_fires_on_l0_spill():
    cfg = CoaxConfig(compact_check_rows=10**6, compact_min_delta=10**9,
                     drift_min_delta=10**9, delta_l0_spill=32)
    idx = COAXIndex(_DS.data[:2_000], cfg)
    for i in range(40):
        idx.insert(_DS.data[i][None])
    # the spill at row 32 forced a check even though the row budget never
    # filled; rows 33..40 bank toward the next one
    assert idx.trigger_checks == 1
    assert idx.delta_primary.spills + idx.delta_outlier.spills == 1


# --------------------------------------------------------------------- #
# The handoff window: old epoch ∪ fresh delta serves during the build
# --------------------------------------------------------------------- #
def _write_until_build_starts(idx, seed0=500, batch=120):
    i = 0
    while idx._handoff_thread is None:
        rows = _more(seed0 + i, batch)
        if i % 3 == 2:
            rows = violate_fd(_DS, rows)
        idx.insert(rows)
        i += 1
        assert i < 60, "background build never triggered"


@pytest.mark.parametrize("backend", [
    "numpy", "device"])
def test_queries_exact_during_background_build(backend):
    dev = backend == "device"
    idx = COAXIndex(_DS.data, BG)
    if dev:
        idx.backend = "device"
    rects = rects_for(_DS.data, n=8)
    _write_until_build_starts(idx)
    _hold_window_open(idx)
    assert idx.epoch == 0 and idx.describe()["background"]["in_flight"]
    for j in range(4):                 # writes + queries inside the window
        idx.insert(_more(900 + j, 50))
        idx.delete(np.arange(j * 11, j * 11 + 7))
        assert_equiv(idx, rects, device=dev, tag=("window", j))
    assert idx.epoch == 0, "held-open window must keep serving the old epoch"
    _release_window(idx)
    assert idx.finish_handoff()
    # the tail replay ticks live counters; a big-enough banked tail may
    # legitimately re-fire a nested SYNC compaction (epoch 2)
    assert idx.epoch >= 1 and idx.background_compactions == 1
    assert idx.compactions == idx.epoch
    d = idx.describe()
    assert d["background"]["completed"] == 1 and not d["background"]["in_flight"]
    assert idx.last_handoff_s > 0
    assert_equiv(idx, rects, device=dev, tag="after-handoff")


def test_sharded_background_compaction_exact():
    sh = ShardedCOAX(_DS.data, BG, n_shards=3, partition="range",
                     partition_dim=0)
    rects = rects_for(_DS.data, n=8)
    for j in range(12):
        rows = _more(700 + j, 150)
        if j % 4 == 3:
            rows = violate_fd(_DS, rows)
        sh.insert(rows)
        sh.delete(np.arange(j * 29, j * 29 + 11))
        if j % 3 == 2:                 # polls happen at query entry
            assert_equiv(sh, rects, scratch=False, tag=("mid", j))
    sh.finish_handoff()
    assert sh.background_compactions >= 1
    d = sh.describe()
    assert d["background"]["completed"] == sh.background_compactions
    assert d["background"]["in_flight"] == 0
    assert d["trigger_checks"] > 0 and len(d["delta_runs"]) == 3
    assert_equiv(sh, rects, tag="sharded-final")


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_prop_interleaved_ops_during_handoff(data):
    """Hypothesis: ANY short interleaving of inserts/deletes applied inside
    a held-open handoff window answers bit-identically to a scratch rebuild,
    and still does after the handoff installs."""
    idx = COAXIndex(_DS.data[:4_000], BG)
    rects = rects_for(_DS.data[:4_000], n=5, extremes=False)
    _write_until_build_starts(idx, seed0=data.draw(
        st.integers(min_value=0, max_value=10**4), label="seed0"))
    _hold_window_open(idx)
    for j in range(data.draw(st.integers(min_value=1, max_value=4),
                             label="n_ops")):
        kind = data.draw(st.sampled_from(["ins", "ins_viol", "del"]),
                         label=f"op{j}")
        if kind == "del":
            lo = data.draw(st.integers(min_value=0, max_value=3_000),
                           label=f"del_lo{j}")
            idx.delete(np.arange(lo, lo + 40))
        else:
            rows = _more(data.draw(st.integers(min_value=0, max_value=10**4),
                                   label=f"seed{j}"),
                         data.draw(st.integers(min_value=1, max_value=80),
                                   label=f"m{j}"))
            idx.insert(violate_fd(_DS, rows) if kind == "ins_viol" else rows)
        assert_equiv(idx, rects, tag=("prop-window", j))
    _release_window(idx)
    idx.finish_handoff()
    assert idx.epoch >= 1
    assert_equiv(idx, rects, tag="prop-after")


# --------------------------------------------------------------------- #
# Crash injection: die inside handoff_rotate, recover via §7
# --------------------------------------------------------------------- #
class _Boom(RuntimeError):
    pass


def test_crash_mid_handoff_recovers_bit_identical(tmp_path):
    import repro.storage.durability as dmod

    idx = COAXIndex(_DS.data, BG)
    Durability.attach(idx, tmp_path)
    oracle = COAXIndex(_DS.data.copy(), SYNC)   # never-crashed sync twin

    def both(op, *args):
        getattr(idx, op)(*args)
        getattr(oracle, op)(*args)

    i = 0
    while idx._handoff_thread is None:          # identical journaled history
        rows = _more(500 + i, 120)
        if i % 3 == 2:
            rows = violate_fd(_DS, rows)
        both("insert", rows)
        i += 1
        assert i < 60
    _hold_window_open(idx)
    for j in range(3):                          # the tail the handoff owes
        both("insert", _more(900 + j, 50))
        both("delete", np.arange(j * 11, j * 11 + 7))
    _release_window(idx)

    # kill the primary INSIDE the rotation: tail re-journaled + fsynced
    # into the new WAL, new snapshot never published (§7.5 crash window)
    orig = dmod.write_snapshot
    dmod.write_snapshot = lambda *a, **k: (_ for _ in ()).throw(_Boom())
    try:
        with pytest.raises(_Boom):
            idx.finish_handoff()
    finally:
        dmod.write_snapshot = orig
    del idx                                     # the crash: memory is gone

    rec = restore(tmp_path, durable=True)
    rects = rects_for(_DS.data, n=8)
    lq, lr = oracle.query_batch(rects)
    q, r = rec.query_batch(rects)
    assert np.array_equal(q, lq) and np.array_equal(r, lr)
    assert rec.epoch == oracle.epoch >= 1       # replay re-fired the build
    assert rec.compactions == oracle.compactions
    assert rec.n_rows == oracle.n_rows
    assert rec._next_id == oracle._next_id
    # amortized-trigger phase converged too (§5.4 counter contract)
    assert rec._write_units == oracle._write_units
    assert rec.trigger_checks == oracle.trigger_checks
    # resume writing on the recovered plane: same trigger timing onwards
    for j in range(4):
        rows = _more(2_000 + j, 120)
        rec.insert(rows)
        oracle.insert(rows)
    rec.finish_handoff()          # a build those writes fired may still run
    assert rec.epoch == oracle.epoch
    assert rec.trigger_checks == oracle.trigger_checks
    q, r = rec.query_batch(rects)
    lq, lr = oracle.query_batch(rects)
    assert np.array_equal(q, lq) and np.array_equal(r, lr)


def test_background_world_converges_with_sync_world():
    """Same op stream, background vs synchronous compaction: query results,
    epochs and trigger phase all converge once the handoff lands."""
    bg = COAXIndex(_DS.data, BG)
    sy = COAXIndex(_DS.data.copy(), SYNC)
    for i in range(14):
        rows = _more(500 + i, 120)
        if i % 3 == 2:
            rows = violate_fd(_DS, rows)
        bg.insert(rows)
        sy.insert(rows)
        if i % 2 == 1:
            dead = np.arange(i * 13, i * 13 + 9)
            bg.delete(dead)
            sy.delete(dead)
    bg.finish_handoff()
    assert sy.compactions >= 1
    assert bg.epoch == sy.epoch
    assert bg.compactions == sy.compactions
    assert bg._write_units == sy._write_units
    assert bg.trigger_checks == sy.trigger_checks
    rects = rects_for(_DS.data, n=8)
    bq, br = bg.query_batch(rects)
    q, r = sy.query_batch(rects)
    assert np.array_equal(bq, q) and np.array_equal(br, r)


# --------------------------------------------------------------------- #
# Device plane: pow2 image bucketing keeps the jit cache flat
# --------------------------------------------------------------------- #
def test_grid_image_pow2_padding():
    from repro.engine.device import _GridImage, _next_pow2
    from repro.kernels.fused_scan import GROUP_ROWS, padded_rows

    for n_rows in (3_000, 9_000):
        idx = COAXIndex(_DS.data[:n_rows], CoaxConfig(auto_compact=False))
        img = _GridImage(idx.primary)
        n = idx.primary.n_rows
        assert img.n_pad >= n + 1                # the dead +inf pad row
        assert img.n_pad == padded_rows(img.n_pad)   # a kernel grid size
        assert img.n_pad == max(GROUP_ROWS, _next_pow2(n + 1))
        assert img.rows.shape == (idx.primary.rows.shape[1],
                                  img.n_pad // 128, 128)


def test_compile_count_flat_across_compaction_epochs():
    cfg = CoaxConfig(compact_min_delta=300, compact_delta_frac=0.01,
                     drift_min_delta=10**9, compact_check_rows=64,
                     delta_l0_spill=64)
    idx = COAXIndex(_DS.data[:6_000], cfg)
    idx.backend = "device"
    rects = rects_for(_DS.data[:6_000], n=8, extremes=False)
    counts = []
    for cycle in range(5):                   # identical op shape per cycle
        epoch_before = idx.epoch
        # rows from the SAME dataset follow the learned FD, so primary and
        # outlier stay inside their pow2 image buckets across epochs
        idx.insert(_DS.data[6_000:6_160])
        idx.query_batch(rects)
        idx.insert(_DS.data[6_160:6_320])    # 320 >= trigger: compacts here
        assert idx.epoch == epoch_before + 1
        idx.query_batch(rects)
        counts.append(idx._coax_plan.compile_count)
    assert counts[-1] == counts[-2] == counts[-3], counts
    # the jit cache and launch counters survived every epoch swap (adopt)
    assert idx._coax_plan.dispatch_count >= 2 * len(counts)
