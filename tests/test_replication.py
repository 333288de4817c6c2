"""Replication plane: WAL shipping, fault injection, failover (DESIGN.md §8).

The load-bearing suite is the failover differential matrix: for every
(workload × replica backend{numpy,device} × fault schedule) cell, a
2-replica ``ReplicatedServer`` runs a deterministic insert/delete/compact
schedule while a ``FaultPlan`` damages the wire (drops, torn frames,
duplicates, reordering, delays, transport errors), crashes a replica
mid-apply, or kills the primary — mid-stream and mid-compaction-rotation.
The §8.7 invariant gates every cell: each replica, once caught up to
frontier F, answers bit-identically to a never-crashed oracle index
replayed to F; promotions must land at a frontier ≥ the last
client-acknowledged write (no data loss).

Satellite coverage: WAL frame-cursor torn-tail/resume semantics, the
frame-aligned-prefix closure property (any intact WAL prefix restores to
a valid, consistent index — hypothesis-driven), idempotent
``Durability.close`` (double-close, close-after-failed-rotation),
graceful-shutdown wiring in ``QueryServer``, and the observability
surface (per-replica frontier/lag/heartbeat, fault + retry counters).
"""
import os
import signal

import numpy as np
import pytest

from repro.core import COAXIndex, CoaxConfig
from repro.engine import QueryServer
from repro.replication import (FaultyTransport, FrameError, InProcTransport,
                               Frame, ReplicatedServer, ReplicationHub,
                               Replica, TransportError, decode_frame,
                               encode_frame, frame_nbytes, seed_state,
                               write_frame)
from repro.runtime.failure import FaultPlan, GracefulShutdown, retry
from repro.storage import (WalFrameCursor, WriteAheadLog, read_wal, restore,
                           wal_path)
from repro.storage.wal import _FILE_HDR, _REC_HDR, OP_INSERT

from hypothesis import given, settings, strategies as st
from workloads import fullscan_expected, mutable_workloads, rects_for, violate_fd

# compaction triggers low enough that the schedules below cross them
TRIG = CoaxConfig(compact_min_delta=300, compact_delta_frac=0.01,
                  drift_min_delta=200)
NOAUTO = CoaxConfig(auto_compact=False)

WORKLOADS = {name: (ds, more) for name, ds, more in mutable_workloads(2_500)}

# ---------------------------------------------------------------------- #
# Fault schedules (≥4, incl. torn shipped frames and primary kills)
# ---------------------------------------------------------------------- #
WIRE = {
    "ship.replica-0": {1: "drop", 3: "tear", 5: "dup", 8: "reorder",
                       11: ("tear", 7)},
    "ship.replica-1": {2: ("delay", 2), 4: ("error", 2), 7: "drop",
                       10: "dup"},
}
REPLICA_CRASH = {
    "replica-0.apply": {4: "crash"},
    "ship.replica-1": {3: "tear", 6: "drop"},
}
KILL_ROTATE = {"primary.rotate": {0: "crash"},
               "ship.replica-0": {2: "tear"}}

SCHEDULES = {"clean": {}, "wire": WIRE, "replica_crash": REPLICA_CRASH}


def _ops(name, n=12, batch=90):
    """Deterministic op stream for a workload: insert bursts (every 4th
    FD-violating) interleaved with deletes of known original ids."""
    ds, more = WORKLOADS[name]
    ops = []
    for i in range(n):
        rows = more(50 + i, batch)
        if i % 4 == 3:
            rows = violate_fd(ds, rows)
        ops.append(("insert", rows))
        if i % 3 == 2:
            ops.append(("delete", np.arange(i * 41, i * 41 + 30)))
    return ops


def _apply(target, op):
    (target.insert if op[0] == "insert" else target.delete)(op[1])


def _assert_identical(a, b, rects, tag):
    ra, ia = a.live_rows()
    rb, ib = b.live_rows()
    assert np.array_equal(ra, rb) and np.array_equal(ia, ib), tag
    ha = a.query_batch_split(rects)
    hb = b.query_batch_split(rects)
    for i in range(len(rects)):
        assert np.array_equal(ha[i], hb[i]), (tag, i)


def _settle(srv, limit=8):
    for _ in range(limit):
        srv.tick()
        if all(not r.alive or r.frontier == srv.hub.frontier
               for r in srv.replicas):
            return
    raise AssertionError("replicas failed to converge: "
                         + str([r.describe() for r in srv.replicas]))


@pytest.fixture(params=["numpy", "device"])
def replica_backend(request):
    if request.param == "device":
        pytest.importorskip("jax")
    return request.param


# ---------------------------------------------------------------------- #
# Convergence matrix: wire damage + replica crashes, no promotion
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("workload", list(WORKLOADS))
@pytest.mark.parametrize("schedule", list(SCHEDULES))
def test_replicas_bit_identical_under_faults(tmp_path, workload, schedule,
                                             replica_backend):
    if replica_backend == "device" and schedule == "clean":
        pytest.skip("device cells run under the fault schedules")
    ds, _ = WORKLOADS[workload]
    plan = FaultPlan({k: dict(v) for k, v in SCHEDULES[schedule].items()})
    idx = COAXIndex(ds.data, TRIG)
    oracle = COAXIndex(ds.data.copy(), TRIG)
    srv = ReplicatedServer(idx, tmp_path, n_replicas=2, plan=plan,
                           replica_backend=replica_backend)
    for i, op in enumerate(_ops(workload)):
        _apply(srv, op)
        _apply(oracle, op)
        if i % 2 == 1:
            srv.tick()
    srv.compact()                        # manual rotation ships F_ROTATE
    oracle.compact()
    dead = [r for r in srv.replicas if not r.alive]
    for r in dead:
        r.revive()                       # crashed replicas resume + catch up
    _settle(srv)

    rects = rects_for(ds.data, n=10, seed=2)
    assert srv.primary.epoch == oracle.epoch >= 1
    for rep in srv.replicas:
        assert rep.frontier == srv.hub.frontier
        assert rep.lag_frames() == 0 and rep.lag_bytes() == 0
        _assert_identical(rep.index, oracle, rects,
                          (workload, schedule, rep.name))
    if schedule == "wire":
        t = srv.transport
        assert t.tears >= 2 and t.drops >= 2 and t.dups >= 2
        assert sum(r.frames_corrupt for r in srv.replicas) >= 2
        assert srv.hub.send_retries >= 1
    if schedule == "replica_crash":
        assert sum(r.crashes for r in dead) == 1


# ---------------------------------------------------------------------- #
# Failover matrix: primary kills, incl. mid-compaction-rotation
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("workload", list(WORKLOADS))
@pytest.mark.parametrize("kill", ["midstream", "mid_rotation_auto",
                                  "mid_rotation_manual"])
def test_failover_no_data_loss(tmp_path, workload, kill, replica_backend):
    if replica_backend == "device" and kill == "mid_rotation_manual":
        pytest.skip("device cells run the midstream and auto-rotation kills")
    ds, _ = WORKLOADS[workload]
    auto = kill == "mid_rotation_auto"
    cfg = TRIG if auto else NOAUTO
    plan = FaultPlan(dict(KILL_ROTATE) if kill != "midstream"
                     else {"ship.replica-0": {2: "tear"}})
    idx = COAXIndex(ds.data, cfg)
    oracle = COAXIndex(ds.data.copy(), cfg)
    srv = ReplicatedServer(idx, tmp_path, n_replicas=2, plan=plan,
                           replica_backend=replica_backend)
    ops = _ops(workload)
    survived = []
    died = False
    for i, op in enumerate(ops):
        try:
            _apply(srv, op)
        except RuntimeError:
            died = True                   # auto-compaction hit the injected
            break                         # rotation crash; op never acked
        survived.append(op)
        if i % 3 == 1:
            srv.tick()                    # replicas lag behind the tail
    if kill == "mid_rotation_manual":
        with pytest.raises(RuntimeError):
            srv.compact()                 # dies inside the §7.5 window
        died = True
    if auto:
        assert died, "schedule never crossed the compaction trigger"
    acked = srv.acked

    srv.kill_primary()
    promoted = srv.promote()
    assert promoted.frontier >= acked     # the no-data-loss gate held
    assert srv.promotions == 1 and srv.primary is promoted.index

    # never-crashed oracle replayed to the promoted frontier: every acked
    # op, plus — for rotation kills — the journaled trigger/compaction
    # (journaled before the crash, hence legitimately recovered)
    for op in survived:
        _apply(oracle, op)
    if died:
        if auto:
            # the fatal op WAS journaled before the primary died; the
            # promoted replica recovered it (frontier > acked is allowed)
            _apply(oracle, ops[len(survived)])
        else:
            oracle.compact()              # rotation completed on disk
    rects = rects_for(ds.data, n=10, seed=2)
    assert promoted.index.epoch == oracle.epoch
    _assert_identical(promoted.index, oracle, rects, (workload, kill))

    # the promoted primary serves writes; survivors re-seed and track it
    _, more = WORKLOADS[workload]
    srv.insert(more(99, 80))
    _apply(oracle, ("insert", more(99, 80)))
    _settle(srv)
    for rep in srv.replicas:
        assert rep.lag_frames() == 0
        _assert_identical(rep.index, oracle, rects,
                          (workload, kill, rep.name))


# ---------------------------------------------------------------------- #
# Shipped-frame codec + transport faults
# ---------------------------------------------------------------------- #
def test_frame_codec_rejects_damage():
    frame = write_frame(3, 7, OP_INSERT, b"\x01\x02\x03\x04")
    data = encode_frame(frame)
    back = decode_frame(data)
    assert back == frame and back.key == (3, 7)
    assert frame_nbytes(frame) == len(data)
    for cut in (1, len(data) // 2, len(data) - 1):
        with pytest.raises(FrameError):
            decode_frame(data[:cut])              # torn in transit
    with pytest.raises(FrameError):
        decode_frame(b"XXXX" + data[4:])          # bad magic
    corrupt = bytearray(data)
    corrupt[-1] ^= 0xFF
    with pytest.raises(FrameError):
        decode_frame(bytes(corrupt))              # payload CRC
    with pytest.raises(FrameError):
        decode_frame(data + b"junk")              # trailing garbage


def test_faulty_transport_actions():
    # m7's successful third attempt consumes event 8, so the tear sits at
    # event 9 and lands on m8
    plan = FaultPlan({"ship.r": {0: "drop", 1: "dup", 2: "reorder",
                                 4: ("delay", 2), 7: ("error", 2),
                                 9: "tear"}})
    t = FaultyTransport(InProcTransport(), plan)
    sent = [f"m{i}".encode() for i in range(10)]
    got = []

    def send(i):
        retry(lambda: t.send("r", sent[i]), retries=3, backoff=0.0,
              retryable=(TransportError,))

    for i in range(10):
        send(i)
        got.extend(t.recv("r"))
    # m0 dropped; m1 twice; m2 held past m3; m4 held 2 sends; m7 delivered
    # after 2 injected errors (retry path); m8 truncated
    assert sent[0] not in got
    assert got.count(sent[1]) == 2
    assert got.index(sent[3]) < got.index(sent[2])
    assert got.index(sent[5]) < got.index(sent[4])
    assert sent[7] in got
    assert any(m == sent[8][:len(m)] and len(m) < len(sent[8]) for m in got)
    assert t.counts() == {"drops": 1, "dups": 1, "tears": 1, "reorders": 1,
                          "delays": 1, "errors": 2}
    assert plan.counts() == {"drop": 1, "dup": 1, "reorder": 1, "delay": 1,
                             "error": 1, "tear": 1}


def test_seed_state_does_not_alias_the_primary(tmp_path):
    ds, more = WORKLOADS["generic_fd"]
    idx = COAXIndex(ds.data, NOAUTO)
    idx.insert(more(1, 60))
    rep = COAXIndex._restore_state(seed_state(idx))
    before = rep.live_rows()
    idx.insert(more(2, 60))               # must not leak into the copy
    idx.delete(np.arange(40))
    after = rep.live_rows()
    assert np.array_equal(before[0], after[0])
    assert np.array_equal(before[1], after[1])
    assert rep.n_rows != idx.n_rows


# ---------------------------------------------------------------------- #
# WalFrameCursor: torn tails, resumability (satellite 3)
# ---------------------------------------------------------------------- #
def _journal(tmp_path, n_ops=6):
    """A real journal + the (kind, payload) records it shipped."""
    ds, more = WORKLOADS["generic_fd"]
    idx = COAXIndex(ds.data, NOAUTO)
    idx.attach_durability(tmp_path / "j")
    shipped = []
    idx.durable.frame_observer = \
        lambda e, s, k, p: shipped.append((s, k, p))
    for i in range(n_ops):
        if i % 3 == 2:
            idx.delete(np.arange(i * 20, i * 20 + 10))
        else:
            idx.insert(more(10 + i, 40))
    idx.durable.sync()
    return idx, wal_path(tmp_path / "j", idx.epoch), shipped


def test_frame_cursor_reads_and_resumes(tmp_path):
    idx, path, shipped = _journal(tmp_path)
    cur = WalFrameCursor(path, expect_epoch=0)
    out = cur.read()
    assert [(s, k, p) for s, k, p in out] == shipped
    assert cur.read() == []               # fully drained
    n0 = len(shipped)                     # the observer keeps appending
    idx.insert(np.zeros((3, idx.n_dims), np.float32))   # live appender
    more_frames = cur.read()
    assert len(more_frames) == 1 and more_frames[0][0] == n0
    assert cur.next_seq == n0 + 1
    # start_seq skips the already-applied prefix
    late = WalFrameCursor(path, expect_epoch=0, start_seq=4)
    assert [s for s, _, _ in late.read()] == list(range(4, n0 + 1))


def test_frame_cursor_pauses_on_torn_tail(tmp_path):
    _, path, shipped = _journal(tmp_path)
    blob = path.read_bytes()
    torn = tmp_path / "torn.log"
    torn.write_bytes(blob[:-11])          # last record torn mid-payload
    cur = WalFrameCursor(torn, expect_epoch=0)
    out = cur.read()
    assert [s for s, _, _ in out] == list(range(len(shipped) - 1))
    assert cur.read() == []               # parked at the torn record
    # ... and RESUMES if the bytes were merely in flight
    torn.write_bytes(blob)
    resumed = cur.read()
    assert [s for s, _, _ in resumed] == [len(shipped) - 1]
    # genuinely corrupt bytes pause it forever
    bad = bytearray(blob)
    bad[-3] ^= 0xFF
    forever = tmp_path / "bad.log"
    forever.write_bytes(bytes(bad))
    cur2 = WalFrameCursor(forever, expect_epoch=0)
    assert [s for s, _, _ in cur2.read()] == list(range(len(shipped) - 1))
    assert cur2.read() == []


def test_frame_cursor_header_cases(tmp_path):
    missing = WalFrameCursor(tmp_path / "nope.log")
    assert missing.read() == []           # missing file reads empty
    stub = tmp_path / "stub.log"
    stub.write_bytes(b"CW")               # header still in flight
    cur = WalFrameCursor(stub)
    assert cur.read() == []
    wal = WriteAheadLog(tmp_path / "w.log", epoch=5)
    wal.close()
    with pytest.raises(ValueError):
        WalFrameCursor(tmp_path / "w.log", expect_epoch=3).read()


# ---------------------------------------------------------------------- #
# Prefix closure: ANY frame-aligned WAL prefix is a valid state (sat. 3)
# ---------------------------------------------------------------------- #
_PREFIX_CACHE = {}


def _prefix_fixture(tmp_path_factory=None):
    if "j" not in _PREFIX_CACHE:
        import tempfile
        from pathlib import Path
        root = Path(tempfile.mkdtemp(prefix="coax_prefix_"))
        ds, more = WORKLOADS["airline"]
        idx = COAXIndex(ds.data, NOAUTO)
        idx.attach_durability(root / "j")
        ops = _ops("airline", n=8, batch=60)
        for op in ops:
            _apply(idx, op)
        idx.durable.sync()
        path = wal_path(root / "j", 0)
        blob = path.read_bytes()
        records, n, intact = read_wal(path, expect_epoch=0)
        assert intact == len(blob) and n == len(ops)
        bounds = [_FILE_HDR.size]
        off = _FILE_HDR.size
        for rec in records:
            _, _, _, plen, _ = _REC_HDR.unpack_from(blob, off)
            off += _REC_HDR.size + plen
            bounds.append(off)
        rects = rects_for(ds.data, n=8, seed=4)
        _PREFIX_CACHE["j"] = (root, ds, ops, blob, bounds, rects)
    return _PREFIX_CACHE["j"]


def _check_prefix(k):
    root, ds, ops, blob, bounds, rects = _prefix_fixture()
    prefix_dir = root / f"prefix_{k}"
    if not (prefix_dir / "wal_00000000.log").exists():
        import shutil
        shutil.copytree(root / "j", prefix_dir)
        os.truncate(prefix_dir / "wal_00000000.log", bounds[k])
    rec = restore(prefix_dir)
    oracle = COAXIndex(ds.data, NOAUTO)
    for op in ops[:k]:
        _apply(oracle, op)
    rows, ids = rec.live_rows()
    orows, oids = oracle.live_rows()
    assert np.array_equal(rows, orows) and np.array_equal(ids, oids)
    want = fullscan_expected(rows, ids, rects)
    got = rec.query_batch_split(rects)
    for i in range(len(rects)):
        assert np.array_equal(got[i], want[i])
    assert rec._next_id == oracle._next_id


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_wal_prefix_closure(data):
    """Replaying any frame-aligned prefix of the journal yields exactly the
    oracle state after that many ops — the §8 shipping protocol's licence
    to resume a replica from an arbitrary applied frontier."""
    root, ds, ops, blob, bounds, rects = _prefix_fixture()
    _check_prefix(data.draw(st.integers(min_value=0, max_value=len(ops)),
                            label="k"))


def test_wal_prefix_closure_exhaustive():
    """Every frame boundary, deterministically — keeps the closure property
    covered on images without hypothesis."""
    _, _, ops, _, bounds, _ = _prefix_fixture()
    assert len(bounds) == len(ops) + 1
    for k in range(len(ops) + 1):
        _check_prefix(k)


# ---------------------------------------------------------------------- #
# Idempotent close + fsync-on-close (satellite 2)
# ---------------------------------------------------------------------- #
def test_durability_close_is_idempotent(tmp_path):
    ds, more = WORKLOADS["generic_fd"]
    idx = COAXIndex(ds.data, NOAUTO)
    idx.attach_durability(tmp_path / "d")
    idx.insert(more(3, 50))
    dur = idx.durable
    assert dur.wal.pending_bytes > 0 and not dur.closed
    dur.close()                           # fsyncs the tail
    assert dur.closed and dur.wal.pending_bytes == 0
    dur.close()                           # double close: no-op, no raise
    dur.sync()                            # sync after close: no-op
    assert dur.wal.nbytes() == (tmp_path / "d" /
                                dur.wal.path.name).stat().st_size
    # the closed journal is complete and recoverable
    rec = restore(tmp_path / "d")
    assert rec.n_rows == idx.n_rows


def test_close_after_failed_rotation(tmp_path, monkeypatch):
    ds, more = WORKLOADS["generic_fd"]
    idx = COAXIndex(ds.data, NOAUTO)
    idx.attach_durability(tmp_path / "d")
    idx.insert(more(3, 50))

    import repro.storage.durability as dmod
    def boom(*a, **k):
        raise OSError("disk full mid-rotation")
    monkeypatch.setattr(dmod, "write_snapshot", boom)
    with pytest.raises(OSError):
        idx.compact()                     # dies before the new pair exists
    monkeypatch.undo()
    dur = idx.durable
    dur.close()                           # old handle still closes cleanly
    dur.close()
    assert dur.closed
    # the §7.5 contract held: the OLD (snapshot, WAL) pair still recovers,
    # and replays the compaction the crash interrupted
    rec = restore(tmp_path / "d", durable=True)
    assert rec.n_rows == idx.n_rows


def test_sharded_close_idempotent(tmp_path):
    from repro.engine import ShardedCOAX
    ds, more = WORKLOADS["generic_fd"]
    sh = ShardedCOAX(ds.data, n_shards=2, config=NOAUTO)
    sh.attach_durability(tmp_path / "s")
    sh.insert(more(5, 40))
    sh.durable.close()
    assert sh.durable.closed
    sh.durable.close()                    # fan-out stays idempotent
    sh.durable.sync()


def test_wal_close_guards(tmp_path):
    wal = WriteAheadLog(tmp_path / "w.log", epoch=0)
    wal.append_delete(np.arange(4))
    wal.close()
    assert wal.closed
    wal.close()                           # idempotent
    wal.pending_bytes = 99                # the failed-rotation zombie state
    wal.sync()                            # must not raise on a dead handle
    assert wal.nbytes() == (tmp_path / "w.log").stat().st_size


# ---------------------------------------------------------------------- #
# Graceful shutdown wiring (satellite 1)
# ---------------------------------------------------------------------- #
def test_query_server_graceful_shutdown(tmp_path):
    ds, more = WORKLOADS["airline"]
    idx = COAXIndex(ds.data, NOAUTO)
    idx.attach_durability(tmp_path / "d")
    rects = rects_for(ds.data, n=12, seed=1)
    with GracefulShutdown(signals=(signal.SIGTERM,)) as stop:
        srv = QueryServer(idx, max_batch=4, shutdown=stop)
        for r in rects:
            srv.submit(r)
        srv.insert(more(7, 50))
        first = srv.drain(max_waves=1)
        assert len(first) == 4 and not srv.shutdown_requested
        os.kill(os.getpid(), signal.SIGTERM)    # the real preemption signal
        assert srv.shutdown_requested
        srv.insert(more(8, 30))
        rest = srv.drain()                # forms no new waves
        assert rest == {}
        assert len(srv) == len(rects) - 4  # queries kept for the successor
        srv.close()                       # flush writes + fsync + release
    assert srv.closed and len(srv._write_queue) == 0
    assert idx.durable.closed and idx.durable.wal_pending_bytes == 0
    srv.close()                           # close is idempotent too
    rec = restore(tmp_path / "d")         # every flushed write survived
    assert rec.n_rows == idx.n_rows
    st_ = srv.stats()
    assert st_["shutdown_requested"] and st_["closed"]


# ---------------------------------------------------------------------- #
# Observability (satellite 6)
# ---------------------------------------------------------------------- #
def test_replication_stats_surface(tmp_path):
    ds, more = WORKLOADS["generic_fd"]
    plan = FaultPlan({"ship.replica-0": {1: "drop", 3: "dup", 5: "tear"},
                      "ship.replica-1": {2: ("error", 1)}})
    idx = COAXIndex(ds.data, NOAUTO)
    srv = ReplicatedServer(idx, tmp_path, n_replicas=2, plan=plan)
    for i in range(6):
        srv.insert(more(20 + i, 40))
        for rep in srv.replicas:          # pump without heartbeats so the
            rep.pump()                    # plan's event indices stay on the
                                          # write frames alone
    rects = rects_for(ds.data, n=4, seed=0)
    for _ in range(3):
        srv.query_batch_split(rects)
    s = srv.stats()
    assert s["frontier"] == {"epoch": 0, "seq": 6}
    assert s["acked"] == {"epoch": 0, "seq": 6}
    assert s["ship"]["shipped_frames"] == 6
    assert s["ship"]["shipped_bytes"] > 0
    assert s["ship"]["send_retries"] >= 1          # the injected error path
    assert s["transport_faults"]["drops"] == 1
    assert s["transport_faults"]["dups"] == 1
    assert s["transport_faults"]["tears"] == 1
    assert s["fault_plan"] == {"drop": 1, "dup": 1, "tear": 1, "error": 1}
    assert s["reads"]["replica"] == 3 and s["reads"]["degraded"] == 0
    for r in s["replicas"]:
        assert r["alive"] and r["lag_frames"] == 0 and r["lag_bytes"] == 0
        assert (r["epoch"], r["next_seq"]) == (0, 6)
        assert r["heartbeat_age"] < 5.0
        assert r["frames_applied"] >= 6
    r0 = next(r for r in s["replicas"] if r["name"] == "replica-0")
    assert r0["frames_corrupt"] == 1               # the torn frame
    assert r0["frames_duplicate"] >= 1             # the duplicated frame
    assert r0["catchup_fetches"] >= 1              # repaired the drop/tear
    # degradation: every replica unhealthy -> primary serves (counted)
    for rep in srv.replicas:
        rep.alive = False
    srv.query_batch_split(rects)
    s2 = srv.stats()
    assert s2["reads"]["degraded"] == 1 and s2["reads"]["primary"] == 1


def test_promotion_requires_live_replica(tmp_path):
    from repro.replication import ReplicationError
    ds, _ = WORKLOADS["generic_fd"]
    idx = COAXIndex(ds.data, NOAUTO)
    srv = ReplicatedServer(idx, tmp_path, n_replicas=1)
    srv.replicas[0].alive = False
    srv.kill_primary()
    with pytest.raises(ReplicationError):
        srv.promote()
    with pytest.raises(ReplicationError):
        srv.insert(np.zeros((1, ds.data.shape[1]), np.float32))
    with pytest.raises(ReplicationError):
        srv.query_batch_split(rects_for(ds.data, n=2, seed=0))
