"""Durability plane: snapshots, WAL, crash injection, recovery ≡ replay.

The load-bearing suite is the kill-and-recover matrix: for every
(workload × backend{numpy,device} × {single, K=4 sharded}) cell, an index
crashed at an arbitrary point of a deterministic insert/delete schedule and
recovered via snapshot + WAL replay must — after resuming the remaining
ops — return flat (query, row) hits bit-identical to the uninterrupted
index, pre- and post-compaction, with bit-equal Bayesian tracker
statistics (so drift-gated compaction fires at the same op).  Crash
injection covers every window of DESIGN.md §7: torn WAL tails, staged
snapshots that never renamed, stale shard snapshots, rotation interrupted
between snapshot and truncation.
"""
import os
from pathlib import Path

import numpy as np
import pytest

from repro.core import COAXIndex, CoaxConfig
from repro.data import make_airline
from repro.engine import QueryServer, ShardedCOAX
from repro.storage import (WriteAheadLog, atomic, latest_snapshot,
                           read_manifest, read_wal, restore, wal_path,
                           write_snapshot)

from hypothesis import given, settings, strategies as st
from workloads import (NOAUTO, fullscan_expected, mutable_workloads,
                       rects_for, violate_fd)

# compaction triggers low enough that deterministic schedules cross them
TRIG = CoaxConfig(compact_min_delta=400, compact_delta_frac=0.01,
                  drift_min_delta=200)


def _schedule(ds, more, n_ops=16, violate_every=4, delete_every=3):
    """Deterministic op list: insert bursts (every ``violate_every``-th
    FD-violating) interleaved with deletes of known original ids."""
    ops = []
    for i in range(n_ops):
        rows = more(100 + i, 120)
        if i % violate_every == violate_every - 1:
            rows = violate_fd(ds, rows)
        ops.append(("insert", rows))
        if i % delete_every == delete_every - 1:
            ops.append(("delete", np.arange(i * 37, i * 37 + 25)))
    return ops


def _apply(idx, op):
    (idx.insert if op[0] == "insert" else idx.delete)(op[1])


def _flat_hits(idx, rects, backend=None):
    if backend is not None:
        bk = idx.backend
        idx.backend = backend
        out = idx.query_batch(rects)
        idx.backend = bk
        return out
    return idx.query_batch(rects)


def _assert_state_equal(live, rec, rects, tag=""):
    """Every behavioral dimension of bit-identity (DESIGN.md §7.4)."""
    lq, lr = _flat_hits(live, rects)
    q, r = _flat_hits(rec, rects)
    assert np.array_equal(q, lq) and np.array_equal(r, lr), (tag, "hits")
    assert rec.epoch == live.epoch, (tag, "epoch")
    assert rec.compactions == live.compactions, (tag, "compactions")
    assert rec._next_id == live._next_id, (tag, "next_id")
    assert rec.n_rows == live.n_rows, (tag, "n_rows")


def _assert_trackers_equal(live, rec, tag=""):
    """Satellite: recovered Bayesian sufficient statistics must be BIT
    equal to the live tracker's, and the drift score must match exactly."""
    if hasattr(live, "shards"):
        for k, (ls, rs) in enumerate(zip(live.shards, rec.shards)):
            _assert_trackers_equal(ls, rs, (tag, k))
        return
    keys = live._tracker_keys()
    assert rec._tracker_keys() == keys, (tag, "tracker keys")
    for k in keys:
        assert np.array_equal(live._fd_trackers[k].xtx,
                              rec._fd_trackers[k].xtx), (tag, k, "xtx")
        assert np.array_equal(live._fd_trackers[k].xty,
                              rec._fd_trackers[k].xty), (tag, k, "xty")
    assert live._x_scale == rec._x_scale, (tag, "x_scale")
    assert live.drift_predictability() == rec.drift_predictability(), tag


# --------------------------------------------------------------------- #
# atomic.py: the shared staged-write idiom
# --------------------------------------------------------------------- #
def test_atomic_stage_rename_and_completeness(tmp_path):
    def good(tmp):
        (tmp / "payload.bin").write_bytes(b"x" * 64)
        (tmp / "MANIFEST.json").write_text("{}")

    atomic.stage_and_rename(tmp_path / "epoch_00000001_000000000000", good)
    # a crash mid-stage = a .tmp dir that never renamed + a manifest-less dir
    (tmp_path / ".tmp.deadbeef.epoch_00000002_000000000000").mkdir()
    torn = tmp_path / "epoch_00000003_000000000000"
    torn.mkdir()
    (torn / "payload.bin").write_bytes(b"partial")
    latest = atomic.latest_complete(tmp_path, "epoch_")
    assert latest is not None and latest.name == "epoch_00000001_000000000000"
    assert atomic.parse_key(latest.name, "epoch_") == (1, 0)
    assert atomic.sweep_stale_tmp(tmp_path) == 1


def test_atomic_retention_keeps_newest(tmp_path):
    def writer(tmp):
        (tmp / "MANIFEST.json").write_text("{}")

    for step in range(5):
        atomic.stage_and_rename(tmp_path / f"step_{step:08d}", writer)
    assert atomic.retain(tmp_path, "step_", keep=2) == 3
    keys = [k for k, _ in atomic.complete_entries(tmp_path, "step_")]
    assert keys == [(3,), (4,)]


def test_atomic_failed_stage_leaves_previous(tmp_path):
    def writer(tmp):
        (tmp / "MANIFEST.json").write_text('{"v": 1}')

    atomic.stage_and_rename(tmp_path / "step_00000001", writer)

    def boom(tmp):
        (tmp / "junk").write_bytes(b"j")
        raise RuntimeError("disk full")

    with pytest.raises(RuntimeError):
        atomic.stage_and_rename(tmp_path / "step_00000001", boom)
    assert (tmp_path / "step_00000001" / "MANIFEST.json").read_text() == '{"v": 1}'
    assert not list(tmp_path.glob(".tmp.*"))


# --------------------------------------------------------------------- #
# wal.py: framing, torn tails
# --------------------------------------------------------------------- #
def test_wal_roundtrip(tmp_path):
    p = wal_path(tmp_path, 3)
    wal = WriteAheadLog(p, epoch=3)
    rows = np.arange(12, dtype=np.float32).reshape(3, 4)
    wal.append_insert(rows, np.array([7, 8, 9], np.int64))
    wal.append_delete(np.array([1, 2], np.int64))
    assert wal.pending_records == 2 and wal.pending_bytes > 0
    wal.sync()
    assert wal.pending_bytes == 0
    wal.close()
    records, next_seq, intact = read_wal(p, expect_epoch=3)
    assert next_seq == 2 and intact == p.stat().st_size
    assert np.array_equal(records[0].rows, rows)
    assert np.array_equal(records[0].ids, [7, 8, 9])
    assert records[1].rows is None
    assert np.array_equal(records[1].ids, [1, 2])
    with pytest.raises(ValueError):
        read_wal(p, expect_epoch=4)


@pytest.mark.parametrize("cut", [1, 10, 21, 30])
def test_wal_torn_tail_recovers_prefix(tmp_path, cut):
    """Truncating the WAL mid-record (any byte of the last frame) must
    yield exactly the complete-prefix records."""
    p = wal_path(tmp_path, 0)
    wal = WriteAheadLog(p, epoch=0)
    for i in range(3):
        wal.append_insert(np.full((2, 2), i, np.float32),
                          np.array([2 * i, 2 * i + 1], np.int64))
    wal.close()
    full = p.stat().st_size
    records, _, _ = read_wal(p)
    assert len(records) == 3
    os.truncate(p, full - cut)              # torn write: lose tail bytes
    records, next_seq, intact = read_wal(p)
    assert len(records) == 2 and next_seq == 2
    assert intact <= full - cut
    # garbage tail (not just truncation) must also stop at the prefix
    # (0xff can never complete the torn record: its true bytes differ)
    with open(p, "ab") as f:
        f.write(b"\xff" * 40)
    records, next_seq, _ = read_wal(p)
    assert len(records) == 2 and next_seq == 2


# --------------------------------------------------------------------- #
# snapshot round trip
# --------------------------------------------------------------------- #
def test_snapshot_roundtrip_midepoch(tmp_path):
    """A full-state save with live deltas, tombstones and dragged trackers
    restores bit-identically — no WAL involved."""
    name, ds, more = mutable_workloads(4000)[0]
    idx = COAXIndex(ds.data, NOAUTO)
    idx.insert(more(100, 300))
    idx.insert(violate_fd(ds, more(101, 80)))
    idx.delete(np.arange(50, 120))
    path = idx.save(tmp_path)
    man = read_manifest(path)
    assert man["kind"] == "coax" and man["wal_seq"] == 0
    rects = rects_for(ds.data, n=8)
    rec = COAXIndex.restore(tmp_path)
    _assert_state_equal(idx, rec, rects, "roundtrip")
    _assert_trackers_equal(idx, rec, "roundtrip")
    # the restored index keeps mutating correctly: scratch-oracle agreement
    rec.insert(more(102, 60))
    idx.insert(more(102, 60))
    rows, ids = idx.live_rows()
    want = fullscan_expected(rows, ids, rects)
    got = rec.query_batch_split(rects)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


def test_snapshot_newest_complete_wins(tmp_path):
    ds = make_airline(3000, seed=3)
    idx = COAXIndex(ds.data, NOAUTO)
    write_snapshot(idx, tmp_path, wal_seq=0)
    idx.insert(make_airline(100, seed=9).data)
    newer = write_snapshot(idx, tmp_path, wal_seq=5)
    assert latest_snapshot(tmp_path) == newer
    # a staged-but-never-renamed snapshot must not shadow it
    (tmp_path / ".tmp.cafef00d.epoch_00000009_000000000000").mkdir()
    bogus = tmp_path / "epoch_00000009_000000000000"
    bogus.mkdir()
    (bogus / "arrays.npz").write_bytes(b"not an npz")
    assert latest_snapshot(tmp_path) == newer
    rec = restore(tmp_path)
    assert rec.n_rows == idx.n_rows


# --------------------------------------------------------------------- #
# kill-and-recover differential matrix (the acceptance test)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("wname", ["airline", "osm", "generic_fd"])
@pytest.mark.parametrize("shards", [None, 4])
def test_kill_and_recover_matrix(tmp_path, wname, shards):
    """Crash at arbitrary points of a deterministic schedule; recover;
    resume the remaining ops; flat hits must be bit-identical to the
    uninterrupted index on numpy AND device, pre- and post-compaction."""
    name, ds, more = next(w for w in mutable_workloads(5000) if w[0] == wname)
    rects = rects_for(ds.data, n=8)
    ops = _schedule(ds, more)

    def build():
        if shards:
            return ShardedCOAX(ds.data, TRIG, n_shards=shards)
        return COAXIndex(ds.data, TRIG)

    live = build()
    compact_ops = []                      # ops after which a compaction fired
    for i, op in enumerate(ops):
        before = live.compactions
        _apply(live, op)
        if live.compactions != before:
            compact_ops.append(i)
    assert compact_ops, "schedule must cross the compaction trigger"

    # crash points: start, pre-first-compaction, right at it, and the end
    points = sorted({0, max(compact_ops[0] - 1, 0), compact_ops[0] + 1,
                     len(ops)})
    for crash_at in points:
        d = tmp_path / f"crash_{crash_at}"
        vic = build()
        vic.attach_durability(d)
        for op in ops[:crash_at]:
            _apply(vic, op)
        vic.durable.sync()
        del vic                            # the crash: memory is gone
        rec = restore(d, durable=True)
        assert type(rec) is type(live)
        for op in ops[crash_at:]:
            _apply(rec, op)
        _assert_state_equal(live, rec, rects, (wname, shards, crash_at))
        _assert_trackers_equal(live, rec, (wname, shards, crash_at))
        lq, lr = _flat_hits(live, rects, backend="device")
        q, r = _flat_hits(rec, rects, backend="device")
        assert np.array_equal(q, lq) and np.array_equal(r, lr), \
            (wname, shards, crash_at, "device")


def test_recover_preserves_compaction_schedule(tmp_path):
    """After recovery the drift/size triggers fire at the SAME op as the
    never-crashed index — the tracker-seeding satellite's observable."""
    name, ds, more = mutable_workloads(5000)[0]
    ops = _schedule(ds, more, n_ops=20)
    live = COAXIndex(ds.data, TRIG)
    d = Path(tmp_path) / "dur"
    vic = COAXIndex(ds.data, TRIG).attach_durability(d)
    crash_at = 6
    live_epochs, rec_epochs = [], []
    for op in ops[:crash_at]:
        _apply(live, op)
        _apply(vic, op)
    vic.durable.sync()
    del vic
    rec = restore(d, durable=True)
    for op in ops[crash_at:]:
        _apply(live, op)
        live_epochs.append(live.epoch)
        _apply(rec, op)
        rec_epochs.append(rec.epoch)
    assert live_epochs == rec_epochs      # compactions at identical ops
    assert live.compactions == rec.compactions > 0


# --------------------------------------------------------------------- #
# crash injection
# --------------------------------------------------------------------- #
def test_truncated_wal_recovers_to_durable_prefix(tmp_path):
    """Kill mid-append: the torn record is dropped, recovery lands exactly
    on the scratch-rebuild oracle of the ops that survived, and the
    re-attached journal keeps working from there."""
    name, ds, more = mutable_workloads(4000)[0]
    rects = rects_for(ds.data, n=6)
    idx = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    idx.insert(more(100, 200))
    idx.delete(np.arange(40))
    idx.durable.sync()
    oracle_rows, oracle_ids = idx.live_rows()
    idx.insert(more(101, 150))            # will be torn mid-record
    idx.durable.close()
    p = wal_path(tmp_path, 0)
    os.truncate(p, p.stat().st_size - 17)

    rec = restore(tmp_path, durable=True)
    want = fullscan_expected(oracle_rows, oracle_ids, rects)
    got = rec.query_batch_split(rects)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    # the truncated tail was cut, so appending resumes at the right seq
    rec.insert(more(102, 50))
    rec.durable.sync()
    records, next_seq, intact = read_wal(p, expect_epoch=0)
    assert next_seq == 3 and intact == p.stat().st_size
    rec2 = restore(tmp_path)
    assert rec2.n_rows == rec.n_rows


def test_crash_between_stage_and_rename(tmp_path):
    """Kill after staging a checkpoint but before the rename: the .tmp
    litter is invisible, recovery uses the previous snapshot + full WAL."""
    name, ds, more = mutable_workloads(4000)[0]
    rects = rects_for(ds.data, n=6)
    idx = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    idx.insert(more(100, 300))
    idx.delete(np.arange(60))
    idx.durable.sync()
    lq, lr = idx.query_batch(rects)
    # simulate the checkpoint dying mid-stage: payload written, no rename
    litter = tmp_path / ".tmp.00c0ffee.epoch_00000000_000000000002"
    litter.mkdir()
    (litter / "arrays.npz").write_bytes(b"half-written")
    (litter / "manifest.json").write_text("{}")
    del idx
    rec = restore(tmp_path, durable=True)
    q, r = rec.query_batch(rects)
    assert np.array_equal(q, lq) and np.array_equal(r, lr)
    assert not list(tmp_path.glob(".tmp.*"))   # recovery swept the litter
    man = read_manifest(latest_snapshot(tmp_path))
    assert man["wal_seq"] == 0                 # the staged one never won


def test_rotation_crash_window_snapshot_published_wal_not_cut(tmp_path):
    """Kill between the rotation's snapshot rename and the old-WAL delete:
    the newest snapshot wins and the stale WAL is ignored AND cleaned."""
    name, ds, more = mutable_workloads(4000)[0]
    rects = rects_for(ds.data, n=6)
    idx = COAXIndex(ds.data, TRIG).attach_durability(tmp_path)
    while idx.compactions == 0:                # drive across the trigger
        idx.insert(more(103, 120))
    idx.durable.sync()
    lq, lr = idx.query_batch(rects)
    assert idx.epoch >= 1
    # resurrect a stale pre-rotation WAL as the crash would leave it
    stale = wal_path(tmp_path, idx.epoch - 1)
    WriteAheadLog(stale, idx.epoch - 1).close()
    del idx
    rec = restore(tmp_path, durable=True)
    q, r = rec.query_batch(rects)
    assert np.array_equal(q, lq) and np.array_equal(r, lr)
    assert not stale.exists()                  # recovery cleaned it


def test_midreplay_compaction_defers_rotation(tmp_path):
    """Crash BETWEEN the WAL append of a trigger-tripping op and the
    rotation's disk work: the WAL still holds the op, replay re-fires the
    compaction, and the deferred rotation leaves a crash-safe pair — a
    second recovery lands on the identical state."""
    name, ds, more = mutable_workloads(4000)[0]
    rects = rects_for(ds.data, n=6)
    live = COAXIndex(ds.data, TRIG)
    d = tmp_path / "dur"
    vic = COAXIndex(ds.data, TRIG).attach_durability(d)
    burst = 0
    while True:                            # stop just before the trigger
        rows = more(200 + burst, 120)
        load = vic.delta_rows + vic.tombstone_count + rows.shape[0]
        if load >= max(TRIG.compact_min_delta,
                       int(TRIG.compact_delta_frac * vic.data.shape[0])):
            break
        live.insert(rows)
        vic.insert(rows)
        burst += 1
    assert vic.compactions == 0
    # the fatal op: journaled, applied, compacts in memory — but the
    # process dies before on_compact's disk work runs
    vic.durable.on_compact = lambda index: None
    live.insert(rows)
    vic.insert(rows)
    assert vic.compactions == 1
    vic.durable.sync()
    del vic
    # on disk: epoch-0 snapshot + a WAL whose last record trips the trigger
    assert wal_path(d, 0).exists() and not wal_path(d, 1).exists()
    rec = restore(d, durable=True)
    _assert_state_equal(live, rec, rects, "midreplay")
    _assert_trackers_equal(live, rec, "midreplay")
    # deferred rotation converged disk: new-epoch pair, old WAL gone
    assert not wal_path(d, 0).exists() and wal_path(d, rec.epoch).exists()
    del rec
    rec2 = restore(d, durable=True)        # crash right after recovery
    _assert_state_equal(live, rec2, rects, "midreplay-again")
    _assert_trackers_equal(live, rec2, "midreplay-again")


def test_attach_truncates_recordless_torn_tail(tmp_path):
    """A first append that died mid-record leaves a recordless torn WAL;
    re-attaching must cut it so later appends stay readable."""
    ds = make_airline(2000, seed=3)
    idx = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    idx.insert(make_airline(40, seed=9).data)
    idx.durable.close()
    p = wal_path(tmp_path, 0)
    os.truncate(p, p.stat().st_size - 11)  # tear the ONLY record
    assert read_wal(p)[1] == 0
    idx2 = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    idx2.insert(make_airline(30, seed=10).data)
    idx2.durable.sync()
    records, next_seq, intact = read_wal(p, expect_epoch=0)
    assert next_seq == 1 and intact == p.stat().st_size
    assert records[0].rows.shape[0] == 30


def test_from_index_refuses_journaled_donor(tmp_path):
    from repro.engine import BatchQueryExecutor

    ds = make_airline(2000, seed=3)
    idx = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    with pytest.raises(ValueError, match="journaled"):
        ShardedCOAX.from_index(idx, 2)
    with pytest.raises(ValueError, match="journaled"):
        BatchQueryExecutor(idx, shards=2)   # the server/executor route


def test_republish_crash_window_repairable(tmp_path):
    """A same-key republish that dies between its two renames leaves the
    old artifact under .old.<nonce>.<name>; the durable-recovery sweep
    renames it back instead of losing the only snapshot."""
    ds = make_airline(2000, seed=3)
    idx = COAXIndex(ds.data, NOAUTO)
    snap = write_snapshot(idx, tmp_path, wal_seq=0)
    # simulate the window: old renamed aside, new never landed
    backup = tmp_path / f".old.deadbeef.{snap.name}"
    os.rename(snap, backup)
    assert latest_snapshot(tmp_path) is None
    assert atomic.sweep_stale_tmp(tmp_path) == 1
    assert latest_snapshot(tmp_path) is not None
    rec = restore(tmp_path)
    assert rec.n_rows == idx.n_rows


def test_stale_shard_snapshot_recovers_from_wal(tmp_path):
    """One shard's snapshot is old (its later checkpoints deleted) while
    its WAL holds the whole epoch tail — per-shard recovery replays it and
    the plane still matches the uninterrupted index exactly."""
    name, ds, more = mutable_workloads(4000)[0]
    rects = rects_for(ds.data, n=6)
    live = ShardedCOAX(ds.data, NOAUTO, n_shards=3)
    vic = ShardedCOAX(ds.data, NOAUTO, n_shards=3).attach_durability(tmp_path)
    ops = _schedule(ds, more, n_ops=6)
    for op in ops[:3]:
        _apply(live, op)
        _apply(vic, op)
    vic.durable.checkpoint()                   # every shard snapshots @ mid
    for op in ops[3:]:
        _apply(live, op)
        _apply(vic, op)
    vic.durable.checkpoint()
    vic.durable.sync()
    del vic
    # stale-snapshot injection: shard 1 loses every snapshot newer than
    # its epoch-0 build snapshot, keeping only the WAL
    sdir = tmp_path / "shard_01"
    entries = atomic.complete_entries(sdir, "epoch_", "manifest.json")
    assert len(entries) >= 2
    import shutil
    for _, p in entries[1:]:
        shutil.rmtree(p)
    # crash litter inside a SHARD directory must be swept on recovery too
    (sdir / ".tmp.0badc0de.epoch_00000000_000000000009").mkdir()
    rec = restore(tmp_path, durable=True)
    _assert_state_equal(live, rec, rects, "stale-shard")
    _assert_trackers_equal(live, rec, "stale-shard")
    assert not list(sdir.glob(".tmp.*"))


def test_attach_refuses_live_history(tmp_path):
    ds = make_airline(2000, seed=3)
    idx = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    idx.insert(make_airline(50, seed=9).data)
    idx.durable.sync()
    fresh = COAXIndex(ds.data, NOAUTO)
    with pytest.raises(ValueError, match="journal records"):
        fresh.attach_durability(tmp_path)
    # a newer-keyed snapshot alone (no WAL records) must also refuse: it
    # would shadow the fresh index's history at restore time
    idx.durable.checkpoint()
    os.unlink(wal_path(tmp_path, 0))
    with pytest.raises(ValueError, match="newer"):
        fresh.attach_durability(tmp_path)


# --------------------------------------------------------------------- #
# server + stats surfacing
# --------------------------------------------------------------------- #
def test_server_wave_sync_checkpoint_and_recover(tmp_path):
    name, ds, more = mutable_workloads(4000)[0]
    rects = rects_for(ds.data, n=10)
    idx = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    srv = QueryServer(idx, max_batch=4, checkpoint_every=2)
    srv.insert(more(100, 80))
    srv.delete(np.arange(30))
    for r in rects:
        srv.submit(r)
    res = srv.drain()
    s = srv.stats()
    assert s["wal_records"] == 2
    assert s["wal_pending_bytes"] == 0          # synced at wave boundaries
    assert s["checkpoints_written"] >= 1
    assert s["last_snapshot_bytes"] > 0
    man = read_manifest(latest_snapshot(tmp_path))
    assert man["wal_seq"] == 2                  # checkpoint absorbed the ops
    del srv, idx
    srv2 = QueryServer.recover(tmp_path, max_batch=4)
    for r in rects:
        srv2.submit(r)
    res2 = srv2.drain()
    assert all(np.array_equal(a, b)
               for a, b in zip(res.values(), res2.values()))


def test_describe_and_footprint_surface_durability(tmp_path):
    name, ds, more = mutable_workloads(3000)[0]
    idx = COAXIndex(ds.data, NOAUTO)
    base_fp = idx.memory_footprint()
    assert idx.describe()["durability"] is None
    idx.attach_durability(tmp_path)
    idx.insert(more(100, 64))
    d = idx.describe()["durability"]
    assert d["wal_records"] == 1 and d["wal_pending_bytes"] > 0
    assert d["last_snapshot_bytes"] > 0 and d["snapshots"] == 1
    assert idx.memory_footprint() >= base_fp + d["wal_pending_bytes"]
    idx.durable.sync()
    assert idx.describe()["durability"]["wal_pending_bytes"] == 0
    # sharded rollup
    sh = ShardedCOAX(ds.data, NOAUTO, n_shards=2)
    sh.attach_durability(tmp_path / "sharded")
    sh.insert(more(101, 32))
    sd = sh.describe()["durability"]
    assert len(sd["per_shard"]) == 2 and sd["wal_records"] >= 1


def test_restore_readonly_leaves_directory_untouched(tmp_path):
    """durable=False is the cold-start-replica path: byte-for-byte no
    directory mutation, and the loaded index does not journal."""
    name, ds, more = mutable_workloads(3000)[0]
    idx = COAXIndex(ds.data, NOAUTO).attach_durability(tmp_path)
    idx.insert(more(100, 100))
    idx.durable.sync()
    before = sorted((str(p.relative_to(tmp_path)), p.stat().st_size)
                    for p in tmp_path.rglob("*") if p.is_file())
    rec = restore(tmp_path)
    assert rec.durable is None
    rec.insert(more(101, 10))              # mutates memory only
    after = sorted((str(p.relative_to(tmp_path)), p.stat().st_size)
                   for p in tmp_path.rglob("*") if p.is_file())
    assert before == after


# --------------------------------------------------------------------- #
# property test: arbitrary op sequences, crash point mid-sequence
# --------------------------------------------------------------------- #
_DS = None


def _dataset():
    global _DS
    if _DS is None:
        _DS = mutable_workloads(2500)[0]
    return _DS


@given(st.data())
@settings(max_examples=10, deadline=None)
def test_recovery_equals_uninterrupted_property(tmp_path_factory, data):
    """Hypothesis: for ANY short op sequence and ANY crash point drawn
    mid-sequence, snapshot+WAL recovery followed by the remaining ops is
    bit-identical to the uninterrupted run."""
    name, ds, more = _dataset()
    n_ops = data.draw(st.integers(min_value=1, max_value=8), label="n_ops")
    ops = []
    for i in range(n_ops):
        kind = data.draw(st.sampled_from(["ins", "ins_bad", "del"]),
                         label=f"op{i}")
        if kind == "del":
            lo = data.draw(st.integers(min_value=0, max_value=2400),
                           label=f"lo{i}")
            ops.append(("delete", np.arange(lo, lo + 40)))
        else:
            seed = data.draw(st.integers(min_value=50, max_value=80),
                             label=f"seed{i}")
            rows = more(seed, 60)
            if kind == "ins_bad":
                rows = violate_fd(ds, rows)
            ops.append(("insert", rows))
    crash_at = data.draw(st.integers(min_value=0, max_value=n_ops),
                         label="crash_at")
    cfg = CoaxConfig(compact_min_delta=150, compact_delta_frac=0.01,
                     drift_min_delta=100)
    rects = rects_for(ds.data, n=4, seed=1)

    live = COAXIndex(ds.data, cfg)
    for op in ops:
        _apply(live, op)
    d = tmp_path_factory.mktemp("wal_prop")
    vic = COAXIndex(ds.data, cfg).attach_durability(d)
    for op in ops[:crash_at]:
        _apply(vic, op)
    vic.durable.sync()
    del vic
    rec = restore(d, durable=True)
    for op in ops[crash_at:]:
        _apply(rec, op)
    _assert_state_equal(live, rec, rects, ("prop", crash_at))
    _assert_trackers_equal(live, rec, ("prop", crash_at))

