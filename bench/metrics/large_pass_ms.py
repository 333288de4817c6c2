"""Mean host time of the drain-time large pass, for waves that have one.

Layer: device plan (``engine/device.py``).  Source: the program's
``device.large`` spans recorded during the window (``repro.obs``
tracing), one for each wave with a query over ``hit_cap``: the pass's
scan and compaction dispatches, the wait for them, and the copy of their
hit positions.  A program without the pass reports nothing.
"""


def read(ctx):
    d = [e["t1"] - e["t0"] for e in ctx.spans
         if e["name"] == "device.large" and e["t1"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
