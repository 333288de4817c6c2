"""Semantic result cache + pinned-epoch MVCC reads (DESIGN.md §9).

The contracts under test:

- §9.1 exactness: with a cache attached, every answer — exact hit,
  containment partial, or miss — is BIT-IDENTICAL to the cache-disabled
  path, across (workload × backend × shard-count).
- §9.2 invalidation: any write (insert, delete, background-compaction
  handoff) moves the version key, so no stale entry can ever answer; on a
  sharded plane each shard keys on its OWN version, never the ambiguous
  aggregate epoch sum.
- §9.3 MVCC: a pinned reader answers bit-identically to pin time across
  concurrent writes and handoff installs, and the old epoch's objects are
  freed only after the last pin releases.
"""
import gc
import weakref

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core import COAXIndex, CoaxConfig
from repro.data import make_airline, make_generic_fd, make_osm
from repro.engine import QueryServer, SemanticCache, ShardedCOAX
from workloads import NOAUTO, rects_for, zipf_rects

BG = CoaxConfig(background_compact=True, compact_min_delta=256,
                compact_delta_frac=0.01, compact_check_rows=32)

_DS = {
    "airline": lambda: make_airline(6_000, seed=3),
    "osm": lambda: make_osm(6_000, seed=3),
    "generic_fd": lambda: make_generic_fd(5_000, 5, ((0, 1), (2, 3)), seed=7),
}


def _mix(data, seed=0):
    """Zipfian hot-rect stream (repeats + nested subsets) plus the standard
    mix (full-range, ±inf, empty) — hits, partials and misses in one wave."""
    return np.concatenate([zipf_rects(data, n=48, n_hot=8, seed=seed),
                           rects_for(data, n=8, seed=seed)])


def _split_equal(got, want, tag=""):
    assert len(got) == len(want), tag
    for i, (a, b) in enumerate(zip(got, want)):
        assert np.array_equal(a, b), (tag, i)


# --------------------------------------------------------------------- #
# §9.1 bit-identity matrix: (workload × backend × shards)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("wl", sorted(_DS))
@pytest.mark.parametrize("backend", ["numpy", "device"])
@pytest.mark.parametrize("shards", [None, 4])
def test_cached_answers_bit_identical(wl, backend, shards):
    if backend == "device":
        pytest.importorskip("jax")
    ds = _DS[wl]()
    rects = _mix(ds.data)
    if shards is None:
        idx = COAXIndex(ds.data, NOAUTO, backend=backend)
    else:
        idx = ShardedCOAX(ds.data, NOAUTO, n_shards=shards, backend=backend)
    want = idx.query_batch_split(rects)         # cache-disabled oracle
    idx.attach_cache(byte_budget=8 << 20)
    _split_equal(idx.query_batch_split(rects), want, (wl, backend, "cold"))
    _split_equal(idx.query_batch_split(rects), want, (wl, backend, "warm"))
    cs = idx.last_cache_stats
    assert cs is not None and cs.hits + cs.partial > 0, (wl, backend, shards)


def test_cache_partial_hits_filter_supersets():
    """Nested rects must answer from containing entries (the §9.1 filter),
    not just byte-identical repeats."""
    ds = _DS["airline"]()
    idx = COAXIndex(ds.data, NOAUTO).attach_cache()
    rects = np.asarray(zipf_rects(ds.data, n=16, n_hot=16, nest_frac=0.0,
                                  seed=5), np.float64)
    idx.query_batch(rects)                      # populate with the supersets
    inner = rects.copy()
    width = inner[:, :, 1] - inner[:, :, 0]
    inner[:, :, 0] += 0.25 * width
    inner[:, :, 1] = np.maximum(inner[:, :, 1] - 0.25 * width, inner[:, :, 0])
    want = COAXIndex(ds.data, NOAUTO).query_batch_split(inner)
    _split_equal(idx.query_batch_split(inner), want, "nested")
    assert idx.last_cache_stats.partial == inner.shape[0]


# --------------------------------------------------------------------- #
# §9.2 invalidation: every write moves the version key
# --------------------------------------------------------------------- #
def test_write_invalidates_cache_entries():
    ds = _DS["airline"]()
    idx = COAXIndex(ds.data, NOAUTO).attach_cache()
    row = ds.data[42]
    rect = np.stack([row.astype(np.float64) - 1e-3,
                     row.astype(np.float64) + 1e-3], axis=-1)[None]

    def q():
        return idx.query_batch_split(rect)[0]

    before = q()
    assert np.array_equal(q(), before)                   # cached repeat
    assert idx.cache.hits == 1
    new_id = idx.insert(row[None])[0]
    after = q()                                          # must see the insert
    assert new_id in after and np.array_equal(
        np.sort(np.append(before, new_id)), after)
    assert idx.cache.invalidations > 0                   # old entry purged
    idx.delete([new_id])
    assert np.array_equal(q(), before)                   # and the delete


def test_handoff_install_invalidates_cache():
    """A background-compaction epoch install is a version bump like any
    other write: post-handoff answers come from the new epoch, never a
    pre-handoff cache entry."""
    ds = _DS["airline"]()
    idx = COAXIndex(ds.data, BG).attach_cache()
    rects = _mix(ds.data)
    idx.query_batch(rects)                               # populate
    rng = np.random.default_rng(9)
    while idx.background_compactions < 1:
        idx.insert(ds.data[rng.integers(0, ds.data.shape[0], 64)])
        idx.poll_handoff(wait=True)
    idx.finish_handoff()
    rows, ids = idx.live_rows()
    want = COAXIndex(rows, NOAUTO, row_ids=ids).query_batch_split(rects)
    _split_equal(idx.query_batch_split(rects), want, "post-handoff")


def test_sharded_cache_keys_on_own_shard_version():
    """Compacting shard 0 must strand ONLY shard 0's entries: shard 1's
    keep hitting (its version never moved), and no key ever contains the
    plane's aggregate epoch sum."""
    ds = _DS["airline"]()
    pl = ShardedCOAX(ds.data, NOAUTO, n_shards=2, partition="range")
    pl.attach_cache()
    rects = np.asarray(zipf_rects(ds.data, n=32, n_hot=8, nest_frac=0.0,
                                  seed=2), np.float64)
    pl.query_batch(rects)
    hits0 = [pl.shards[k].cache.hits for k in range(2)]
    pl.shards[0].compact()                      # moves shard 0's version only
    pl.query_batch(rects)                       # re-keys shard 0, hits shard 1
    assert pl.shards[1].cache.hits > hits0[1]   # shard 1 entries survived
    assert pl.shards[0].cache.invalidations > 0  # shard 0's were purged
    assert pl.epoch == 1                        # aggregate moved ...
    for k in (0, 1):
        assert len(pl.shards[k].cache) > 0
        for vkey, _rect_bytes in pl.shards[k].cache._entries:
            assert vkey[0] == k                           # (shard_id, ...)
            assert vkey[1] == pl.shards[k].epoch          # shard's OWN epoch
    # ... but shard 1's entries still key on ITS epoch 0, not the sum:
    assert all(vkey[1] == 0 for vkey, _ in pl.shards[1].cache._entries)
    rows, ids = pl.live_rows()
    want = COAXIndex(rows, NOAUTO, row_ids=ids).query_batch_split(rects)
    _split_equal(pl.query_batch_split(rects), want, "sharded-post-compact")


# --------------------------------------------------------------------- #
# §9.3 MVCC pins
# --------------------------------------------------------------------- #
def test_pin_epoch_exact_across_background_handoff():
    ds = _DS["airline"]()
    idx = COAXIndex(ds.data, BG)
    rects = _mix(ds.data)
    pin = idx.pin_epoch()
    assert idx.pinned_epochs == [pin.epoch]
    want = pin.query_batch_split(rects)
    _split_equal(idx.query_batch_split(rects), want, "pin == live at pin time")
    old_primary = weakref.ref(idx.primary)
    rng = np.random.default_rng(11)
    while idx.background_compactions < 1:
        idx.insert(ds.data[rng.integers(0, ds.data.shape[0], 64)])
        idx.poll_handoff(wait=True)
    idx.finish_handoff()
    assert idx.epoch > pin.epoch
    live = idx.query_batch_split(rects)
    assert any(not np.array_equal(a, b) for a, b in zip(live, want))
    _split_equal(pin.query_batch_split(rects), want, "pin across handoff")
    assert old_primary() is not None            # pin keeps the old epoch alive
    pin.release()
    gc.collect()
    assert old_primary() is None                # ... and releasing frees it
    assert idx.pinned_epochs == []
    with pytest.raises(RuntimeError):
        pin.query(rects[0])
    pin.release()                               # idempotent


def test_pin_epoch_refcount_and_context_manager():
    ds = _DS["generic_fd"]()
    idx = COAXIndex(ds.data, NOAUTO)
    rects = rects_for(ds.data, n=6)
    p1 = idx.pin_epoch()
    with idx.pin_epoch() as p2:
        assert idx._pins[idx.epoch] == 2
        want = p1.query_batch_split(rects)
        _split_equal(p2.query_batch_split(rects), want, "two pins agree")
    assert idx._pins[idx.epoch] == 1            # p2 released at exit
    p1.release()
    assert idx.pinned_epochs == []


def test_sharded_pin_exact_across_writes():
    ds = _DS["osm"]()
    pl = ShardedCOAX(ds.data, NOAUTO, n_shards=4)
    rects = _mix(ds.data)
    pin = pl.pin_epoch()
    assert len(pin.shard_epochs) == 4
    want = pin.query_batch_split(rects)
    _split_equal(pl.query_batch_split(rects), want, "sharded pin at pin time")
    pl.insert(ds.data[:128])
    pl.compact()
    _split_equal(pin.query_batch_split(rects), want, "sharded pin after writes")
    live = pl.query_batch_split(rects)
    assert any(not np.array_equal(a, b) for a, b in zip(live, want))
    pin.release()
    with pytest.raises(RuntimeError):
        pin.query(rects[0])


def test_server_pin_flushes_queued_writes_first():
    ds = _DS["airline"]()
    srv = QueryServer(COAXIndex(ds.data, NOAUTO), max_batch=16)
    rect = np.stack([ds.data[7].astype(np.float64) - 1e-3,
                     ds.data[7].astype(np.float64) + 1e-3], axis=-1)
    srv.insert(ds.data[7][None])                # queued, not yet applied
    pin = srv.pin_epoch()                       # must flush, then freeze
    assert srv.executor.index.delta_rows > 0
    want = pin.query(rect)
    assert want.size == srv.executor.index.query(rect).size
    srv.insert(ds.data[7][None])
    srv.drain()                                 # applies the second insert
    assert np.array_equal(pin.query(rect), want)
    assert srv.executor.index.query(rect).size == want.size + 1
    pin.release()


# --------------------------------------------------------------------- #
# Eviction under a tiny byte budget
# --------------------------------------------------------------------- #
def test_eviction_respects_byte_budget():
    ds = _DS["airline"]()
    idx = COAXIndex(ds.data, NOAUTO)
    twin = COAXIndex(ds.data, NOAUTO)
    idx.attach_cache(byte_budget=16 << 10)      # ~a handful of entries
    rects = rects_for(ds.data, n=40, seed=1, extremes=False)
    for wave in (rects[:20], rects[20:], rects[:20]):
        got = idx.query_batch_split(wave)
        _split_equal(got, twin.query_batch_split(wave), "evicting")
        assert idx.cache.nbytes <= idx.cache.byte_budget
    assert idx.cache.evictions > 0
    # entries too large for the whole budget are refused, not thrashed
    assert idx.cache.rejections >= 0
    assert len(idx.cache) <= idx.cache.max_entries


def test_cache_rejects_entry_larger_than_budget():
    cache = SemanticCache(byte_budget=256, max_entries=8)
    rect = np.array([[0.0, 1.0], [0.0, 1.0]])
    ids = np.arange(1000, dtype=np.int64)
    rows = np.zeros((1000, 2), np.float32)
    assert not cache.admit((0, 0, 0, 0, 0), rect, ids, rows)
    assert cache.rejections == 1 and len(cache) == 0


# --------------------------------------------------------------------- #
# Executor/server stats plumbing
# --------------------------------------------------------------------- #
def test_server_reports_cache_stats():
    ds = _DS["airline"]()
    srv = QueryServer(COAXIndex(ds.data, NOAUTO), max_batch=16,
                      cache_bytes=8 << 20)
    rects = zipf_rects(ds.data, n=48, n_hot=6, seed=4)
    srv.submit_many(rects)
    srv.drain()
    srv.submit_many(rects)
    srv.drain()
    s = srv.stats()
    assert s["cache_hits"] + s["cache_partial"] > 0
    assert 0.0 < s["cache_hit_rate"] <= 1.0
    assert s["cache_bytes"] > 0
    assert any(w.cache_hits + w.cache_partial > 0
               for w in srv.executor.wave_stats)


# --------------------------------------------------------------------- #
# Zipfian generator properties (tests/workloads.py)
# --------------------------------------------------------------------- #
def test_zipf_rects_deterministic_and_nested():
    ds = _DS["osm"]()
    a = zipf_rects(ds.data, n=64, n_hot=8, seed=3)
    b = zipf_rects(ds.data, n=64, n_hot=8, seed=3)
    assert np.array_equal(a, b)                 # deterministic per seed
    pool = zipf_rects(ds.data, n=256, n_hot=8, nest_frac=0.0, seed=3)
    uniq = {r.tobytes() for r in pool}
    assert len(uniq) <= 8                       # draws come from the hot pool
    # every rect (nested or not) is contained in some hot-pool rect
    hot = np.unique(pool.reshape(pool.shape[0], -1), axis=0).reshape(-1, *a.shape[1:])
    for r in a:
        assert any(np.all(h[:, 0] <= r[:, 0]) and np.all(r[:, 1] <= h[:, 1])
                   for h in hot)
    assert np.all(a[:, :, 0] <= a[:, :, 1])     # well-formed half-open rects


# --------------------------------------------------------------------- #
# Hypothesis: arbitrary query/write interleavings, cached == plain
# --------------------------------------------------------------------- #
_H_DS = make_airline(2_000, seed=13)
_H_RECTS = np.concatenate([
    zipf_rects(_H_DS.data, n=12, n_hot=4, seed=21),
    rects_for(_H_DS.data, n=4, seed=21, extremes=False)])


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.sampled_from("qidc"),
                          st.integers(min_value=0, max_value=15)),
                min_size=1, max_size=12))
def test_cached_equals_plain_under_interleavings(ops):
    """Property: under ANY interleaving of queries, inserts, deletes and
    cache-clears, a cached index answers bit-identically to an uncached
    twin driven through the same schedule (ids align by construction)."""
    cached = COAXIndex(_H_DS.data, NOAUTO).attach_cache(byte_budget=1 << 20)
    plain = COAXIndex(_H_DS.data, NOAUTO)
    inserted = []
    for op, k in ops:
        if op == "q":
            rects = _H_RECTS[k % _H_RECTS.shape[0]:][:4]
            _split_equal(cached.query_batch_split(rects),
                         plain.query_batch_split(rects), ("q", k))
        elif op == "i":
            rows = _H_DS.data[k * 7 % _H_DS.data.shape[0]][None]
            inserted.append((cached.insert(rows)[0], plain.insert(rows)[0]))
            assert inserted[-1][0] == inserted[-1][1]
        elif op == "d" and inserted:
            ca, pa = inserted.pop(k % len(inserted))
            assert cached.delete([ca]) == plain.delete([pa]) == 1
        elif op == "c":
            cached.cache.clear()
    rects = _H_RECTS
    _split_equal(cached.query_batch_split(rects),
                 plain.query_batch_split(rects), "final")
