"""Batched query execution engine (DESIGN.md §2, §4, §5, §6).

The per-call path (``COAXIndex.query``) answers one rect per Python
round-trip; this package turns B queries into one translation pass, one
directory probe and one fused scan, and wraps that in an admission/drain
server modelled on ``runtime.router``'s continuous-batching loop — the same
pattern, applied to range-query traffic instead of decode requests.
Under the mutable lifecycle (§5) the server also admits inserts/deletes,
flushed at wave boundaries so every wave sees one snapshot+delta state.
``ShardedCOAX`` (§6) scales the same contracts *out*: K per-region shards
behind one scatter-gather plane, each with its own FDs, delta and epochs.

``BatchQueryExecutor`` — wave-sliced ``query_batch`` driver with per-wave stats
``QueryServer``        — submit rects/writes, drain in priority/FIFO waves;
                         wave-boundary WAL fsync + checkpoint cadence and the
                         ``recover()`` restart constructor (§7)
``ShardedCOAX``        — sharded scatter-gather serving plane (§6); journals
                         per shard via ``repro.storage`` (§7.6)
``SemanticCache``      — rect-containment result cache, exact by nav⊇filter
                         and version-keyed for free invalidation (§9.1–§9.2)
``EpochPin``           — pinned-epoch MVCC read handle (``ShardedEpochPin``
                         for a plane): bit-identical snapshot reads across
                         background-compaction handoffs (§9.3)
``DevicePlan``         — device-resident serving plane for one grid (§4)
``CoaxDevicePlan``     — the COAX megakernel plan: primary + outlier +
                         delta/tombstone segments fused into ONE kernel
                         launch per wave, hits compacted into device-
                         resident buffers and drained one wave behind the
                         submit (double-buffered by executor/server);
                         imported lazily, on first device use
"""
from .cache import CacheLookup, EpochPin, SemanticCache, ShardedEpochPin
from .executor import BatchQueryExecutor, WaveStats, split_hits
from .server import PendingQuery, QueryServer
from .sharded import ShardedCOAX, partition_rows

__all__ = [
    "BatchQueryExecutor",
    "WaveStats",
    "split_hits",
    "QueryServer",
    "PendingQuery",
    "ShardedCOAX",
    "partition_rows",
    "SemanticCache",
    "CacheLookup",
    "EpochPin",
    "ShardedEpochPin",
    "DevicePlan",
    "CoaxDevicePlan",
]


def __getattr__(name):  # PEP 562: keep jax out of the default import path
    if name in ("DevicePlan", "CoaxDevicePlan"):
        from . import device
        return getattr(device, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
