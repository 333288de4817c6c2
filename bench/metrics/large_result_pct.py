"""Share of queries whose answer the device's large pass served.

Layer: device plan (``engine/device.py``).  Source: the ``queries`` args
of the program's ``device.large`` spans recorded during the window (the
wave's queries with more hits than ``hit_cap``, the executor's
``large_results``), over the executor's ``queries`` counter.  A program
without the pass reports nothing.
"""


def read(ctx):
    q = ctx.served["queries"]
    n = [e["args"]["queries"] for e in ctx.spans
         if e["name"] == "device.large"]
    return 100.0 * sum(n) / q if n and q else None
