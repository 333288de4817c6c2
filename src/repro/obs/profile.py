"""Device profiling hooks (DESIGN.md §10.2, profiling layer).

``profile(logdir)`` wraps a ``jax.profiler`` trace capture around a block
of device waves — XLA compile/execute timelines land in ``logdir`` for
TensorBoard / Perfetto.  A capture that was asked for and cannot start or
stop raises: a measurement never runs on without its trace.  A nested or
concurrent entry yields ``False`` — the outer capture already covers the
block.  The cheap per-wave counters (``transfer_bytes``, ``dispatches``,
``compile_count``) do NOT live here — they fold into the metrics registry
from the device plan itself (§10.1).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional

__all__ = ["profile"]

_active = threading.Lock()    # one capture at a time, process-wide


@contextlib.contextmanager
def profile(logdir: Optional[str], enabled: bool = True) -> Iterator[bool]:
    """Capture a ``jax.profiler`` trace into ``logdir`` over the block.

    Yields True when this call started the capture, False when
    ``enabled``/``logdir`` are falsy or an outer capture is running."""
    if not enabled or not logdir:
        yield False
        return
    if not _active.acquire(blocking=False):
        yield False                       # nested/concurrent: outer wins
        return
    import jax                            # first use only: keeps obs jax-free
    try:
        jax.profiler.start_trace(logdir)
        try:
            yield True
        finally:
            jax.profiler.stop_trace()
    finally:
        _active.release()
