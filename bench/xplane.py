"""Reduces a ``jax.profiler`` trace of the window to device numbers.

Input: the ``.xplane.pb`` a traced run writes, read with
``jax.profiler.ProfileData`` (or anything with the same ``planes`` /
``lines`` / ``events`` shape).  The window is the host annotation
``bench.window``; every interval is clipped to it.

* busy: the union of the operation intervals on each device plane's
  ``XLA Ops`` line, averaged over the device planes; idle is the rest.
* kernel time: the summed durations of the operations whose name, or any
  string stat of the event, contains the kernel's name.
* breakdown: the operations that took most time, by HLO instruction name
  (the TPU trace names an operation by its whole HLO text), and the longest
  idle gaps, each named by the innermost event open at its middle on the
  client's thread (under the ``bench.*`` annotation around it, where that
  is another).
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load(logdir):
    """The newest ``.xplane.pb`` under ``logdir``, as ``ProfileData``."""
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return ProfileData.from_file(str(files[-1]))


def _strings(ev) -> List[str]:
    return [ev.name] + [v for _, v in ev.stats if isinstance(v, str)]


def _op_name(name: str) -> str:
    """``%fusion.91 = s32[...] fusion(...)`` -> ``fusion.91``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _window(host_events) -> Optional[Tuple[float, float]]:
    for ev in host_events:
        if ev.name == WINDOW:
            return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def reduce(pd, kernel: str, top: int = 10) -> dict:
    """``busy_s``, ``window_s``, ``kernel_s``, ``kernel_events``,
    ``device_planes`` and the ``breakdown`` lists for one trace."""
    host_lines = [list(ln.events) for p in pd.planes
                  if p.name.startswith("/host:") for ln in p.lines]
    # gaps are named from the client's own thread, the one with the window
    client = [evs for evs in host_lines if _window(evs) is not None]
    host = client[0] if client else [ev for evs in host_lines for ev in evs]
    devs = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    ops = [[ev for ln in p.lines if ln.name == OPS_LINE
            for ev in ln.events] for p in devs]
    win = _window(host)
    if win is None:
        starts = [e.start_ns for o in ops for e in o]
        ends = [e.start_ns + e.duration_ns for o in ops for e in o]
        win = (min(starts, default=0.0), max(ends, default=0.0))
    w0, w1 = win
    busy, kernel_ns, n_kernel = [], 0.0, 0
    by_name = defaultdict(float)
    gaps = []
    for evs in ops:
        iv = []
        for ev in evs:
            a = max(ev.start_ns, w0)
            b = min(ev.start_ns + ev.duration_ns, w1)
            if b <= a:
                continue
            iv.append((a, b))
            by_name[_op_name(ev.name)] += b - a
            if any(kernel in s for s in _strings(ev)):
                kernel_ns += b - a
                n_kernel += 1
        merged = _union(iv)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": (sum(busy) / len(busy) if busy else 0.0) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "kernel_s": kernel_ns / 1e9,
        "kernel_events": n_kernel,
        "device_planes": len(devs),
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in ops_top],
            "idle_gaps": [[host_context(host, (a + b) / 2), (b - a) / 1e9]
                          for a, b in gaps[:top]],
        },
    }


def host_context(host_events, t: float) -> str:
    """The innermost host event open at ``t``, under its ``bench.*``
    annotation where that is another event."""
    open_at = [ev for ev in host_events
               if ev.start_ns <= t < ev.start_ns + ev.duration_ns]
    if not open_at:
        return "no host event"
    inner = min(open_at, key=lambda ev: ev.duration_ns).name
    bench = [ev for ev in open_at if ev.name.startswith("bench.")]
    if bench:
        outer = min(bench, key=lambda ev: ev.duration_ns).name
        if outer != inner:
            return f"{outer} > {inner}"
    return inner
