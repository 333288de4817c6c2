"""Device time of the wave kernel ``coax_fused_scan`` per query answered.

Layer: kernel.  Source: the profiler trace (the kernel's summed device
time inside the window) over the queries the window answered.
"""


def read(ctx):
    t = ctx.trace
    if not t or t["kernel_s"] <= 0 or not ctx.served["queries"]:
        return None
    return 1e3 * t["kernel_s"] / ctx.served["queries"]
