"""Share of queries answered again on the host after the device found
more hits than its buffer holds (``hit_cap``).

Layer: device plan (``engine/device.py``).  Source: the executor's
``hit_overflows`` and ``queries`` counters over the window.
"""


def read(ctx):
    q = ctx.served["queries"]
    return 100.0 * ctx.served["hit_overflows"] / q if q else None
