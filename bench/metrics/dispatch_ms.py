"""Mean host time of one wave-program launch.

Layer: device plan (``engine/device.py``).  Source: the program's
``device.dispatch`` spans recorded during the window (``repro.obs``
tracing), which cover the jitted call that enqueues the wave.
"""


def read(ctx):
    d = [e["t1"] - e["t0"] for e in ctx.spans
         if e["name"] == "device.dispatch" and e["t1"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
