"""Queries fused into one wave.

Layer: server and executor (``engine/server.py``, ``engine/executor.py``).
Source: the executor's ``queries`` and ``waves`` counters over the window.
"""


def read(ctx):
    w = ctx.served["waves"]
    return ctx.served["queries"] / w if w else None
