"""Share of its roofline that the wave kernel ``coax_fused_scan`` reached.

Layer: kernel.  Source: the profiler trace for the kernel's device time;
``bench/work.py`` for the bytes the work needs (the index's candidate rows
x D x 4 plus hits x 4).  The least time is those bytes over the chip's HBM
bandwidth from ``bench/peaks.json``; HBM is the bound, since the kernel
does a few compares per byte read.  100 x least time / kernel time.
"""


def read(ctx):
    t = ctx.trace
    if not t or t["kernel_s"] <= 0 or not ctx.needed_bytes:
        return None
    least_s = ctx.needed_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["kernel_s"]
