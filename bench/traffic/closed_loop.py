"""Closed loop: a fixed number of queries outstanding, topped up between
``QueryServer.drain()`` calls.

Analysts submitting batches of lookups keep the server full: before every
``drain()`` the client tops the pending set up to ``outstanding`` queries
from the pool, and ``drain()`` answers them all before it returns.  The
window runs from its start to the first return at or after ``seconds``,
and ``answered_qps`` counts the queries answered in those whole calls.

The record says how much of the window was stall.  A stall is a drain
longer than three times the window's median drain; ``stalls`` counts them
and ``stall_s`` sums what each lasted beyond three medians.  The threshold
is this definition, not a setting.

Parameters (the mix's ``arrivals``): ``outstanding``.
"""
from __future__ import annotations

import time

STALL_MEDIANS = 3


def run(srv, rects, order, seconds: float, params: dict, rng, mark,
        clock=time.perf_counter) -> dict:
    del rng                                   # the order is the only draw
    outstanding = int(params["outstanding"])
    rect_of, answers, drain_s = {}, {}, []
    i = 0
    t0 = clock()
    while True:
        with mark("bench.submit"):
            while len(srv) < outstanding:
                j = int(order[i % len(order)])
                rect_of[srv.submit(rects[j])] = j
                i += 1
        t = clock()
        with mark("bench.drain"):
            answers.update(srv.drain())
        drain_s.append(clock() - t)
        elapsed = clock() - t0
        if elapsed >= seconds:
            break
    median = sorted(drain_s)[len(drain_s) // 2]
    over = [d - STALL_MEDIANS * median for d in drain_s
            if d > STALL_MEDIANS * median]
    return {"rect_of": rect_of, "answers": answers,
            "attempted": len(rect_of),
            "metrics": {"answered_qps": len(answers) / elapsed},
            "log": {"drains": len(drain_s), "elapsed_s": elapsed,
                    "outstanding": outstanding,
                    "drain_s": {"min": min(drain_s), "max": max(drain_s),
                                "median": median},
                    "stalls": len(over), "stall_s": sum(over)}}
