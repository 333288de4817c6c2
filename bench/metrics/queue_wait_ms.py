"""Median time a query waited in the server before its wave started.

Layer: server and executor (``engine/server.py``, ``engine/executor.py``).
Source: the ``queue_wait_s`` argument of the program's ``wave`` spans
recorded during the window (``repro.obs`` tracing): for each query of the
wave, the span's start minus the query's arrival stamp.
"""
import statistics


def read(ctx):
    w = [x for e in ctx.spans if e["name"] == "wave"
         for x in e["args"].get("queue_wait_s", ())]
    return 1e3 * statistics.median(w) if w else None
