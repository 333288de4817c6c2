"""Mean host time of copying one wave's results off the device.

Layer: device plan (``engine/device.py``).  Source: the program's
``device.copy`` spans recorded during the window (``repro.obs``
tracing), one a wave: the device-to-host copies of the compacted counts,
hit positions and scanned rows, after ``device.wait`` has fenced the
wave's execution.
"""


def read(ctx):
    d = [e["t1"] - e["t0"] for e in ctx.spans
         if e["name"] == "device.copy" and e["t1"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
