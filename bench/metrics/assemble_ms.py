"""Mean host time of assembling one wave's answer.

Layer: device plan (``engine/device.py``).  Source: the program's
``device.assemble`` spans recorded during the window (``repro.obs``
tracing), one a wave: mapping the device's hit positions to row ids,
placing each query's ids and sorting them.  A program without the span
reports nothing.
"""


def read(ctx):
    d = [e["t1"] - e["t0"] for e in ctx.spans
         if e["name"] == "device.assemble" and e["t1"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
