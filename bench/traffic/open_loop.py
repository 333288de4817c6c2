"""Open loop: queries due on a fixed schedule, whatever the server does.

Independent users send lookups at ``rate_qps``.  The window holds
``round(rate_qps * seconds)`` arrivals whose gaps are the exponential
distribution's quantiles at the midpoints of ``n`` equal strata, shuffled
by the seed and scaled so the arrivals fill ``[0, seconds)``: a Poisson
stream in shape, with the same set of gaps for every seed, so seeds change
the order of the work and not its amount.

The client submits every query that is due, then calls
``QueryServer.drain()``; arrivals that fall due during a drain are
submitted when it returns.  A query's latency runs from its due time to
the return of the drain that answered it, and the run serves until every
query due in the window is answered.  ``lateness`` is how far behind its
due time each submit ran, over all submits and over those that fell due
while the client was idle (the generator's own lateness).

Parameters (the mix's ``arrivals``): ``rate_qps``.
"""
from __future__ import annotations

import time

import numpy as np


def arrival_times(rate_qps: float, seconds: float, rng) -> np.ndarray:
    n = max(int(round(rate_qps * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_qps
    rng.shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


def _pct(x, q) -> float:
    return float(np.percentile(x, q)) if len(x) else 0.0


def run(srv, rects, order, seconds: float, params: dict, rng, mark,
        clock=time.perf_counter) -> dict:
    due = arrival_times(float(params["rate_qps"]), seconds, rng)
    n = due.size
    rect_of, due_of, answers, latency = {}, {}, {}, {}
    late_all, late_idle = [], []
    drains, i, busy_until = 0, 0, -1.0
    t0 = clock()
    while i < n or len(srv):
        now = clock() - t0
        if not len(srv) and due[i] > now:
            with mark("bench.wait"):
                time.sleep(due[i] - now)
            continue
        with mark("bench.submit"):
            while i < n and due[i] <= now:
                j = int(order[i % len(order)])
                qid = srv.submit(rects[j], arrival=t0 + due[i])
                rect_of[qid], due_of[qid] = j, due[i]
                late_all.append(now - due[i])
                if due[i] > busy_until:
                    late_idle.append(now - due[i])
                i += 1
        if len(srv):
            with mark("bench.drain"):
                res = srv.drain()
            busy_until = clock() - t0
            drains += 1
            for q in res:
                latency[q] = busy_until - due_of[q]
            answers.update(res)
    lat_ms = np.array(list(latency.values())) * 1e3
    return {"rect_of": rect_of, "answers": answers, "attempted": n,
            "latency_s": latency,
            "metrics": {"query_p50_ms": _pct(lat_ms, 50),
                        "query_p95_ms": _pct(lat_ms, 95)},
            "log": {"drains": drains, "arrivals": n,
                    "served_s": busy_until,
                    "lateness_ms": {
                        "p50": _pct(late_all, 50) * 1e3,
                        "p95": _pct(late_all, 95) * 1e3,
                        "max": max(late_all, default=0.0) * 1e3},
                    "idle_lateness_ms": {
                        "n": len(late_idle),
                        "p50": _pct(late_idle, 50) * 1e3,
                        "p95": _pct(late_idle, 95) * 1e3,
                        "max": max(late_idle, default=0.0) * 1e3}}}
