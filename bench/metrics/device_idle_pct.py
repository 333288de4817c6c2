"""Share of the traced window in which no operation ran on the device.

Layer: device.  Source: the profiler trace (``xplane.reduce``), 100 x
(1 - busy / window), busy being the union of the device's operation
intervals inside the ``bench.window`` annotation.
"""


def read(ctx):
    t = ctx.trace
    if not t or t["window_s"] <= 0 or not t["device_planes"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
