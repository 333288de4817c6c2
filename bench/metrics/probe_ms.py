"""Mean host time of one wave's directory probe.

Layer: device plan (``engine/device.py``).  Source: the program's
``device.probe`` spans recorded during the window (``repro.obs``
tracing), one a wave: the f64 directory probes of both grids and the
outlier grid's bounding-box test.
"""


def read(ctx):
    d = [e["t1"] - e["t0"] for e in ctx.spans
         if e["name"] == "device.probe" and e["t1"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
