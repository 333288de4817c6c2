"""Mean host time of building and uploading one wave's inputs.

Layer: device plan (``engine/device.py``).  Source: the program's
``device.inputs`` spans recorded during the window (``repro.obs``
tracing), one a wave: the per-segment bounds, probe ranges and sort
bands, their upload, the delta segment and any liveness or delta
refresh.
"""


def read(ctx):
    d = [e["t1"] - e["t0"] for e in ctx.spans
         if e["name"] == "device.inputs" and e["t1"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
