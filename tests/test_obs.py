"""Telemetry plane (DESIGN.md §10): registry semantics, tracing structure,
watchdog attribution, and — the gate everything else hangs off — telemetry
on/off bit-identity of query answers.

Quantile checks compare the log-bucketed histogram against a numpy oracle:
the bucket geometry (×2 growth) bounds any reported quantile inside one
bucket of the true order statistic, so the assertions use that factor-of-2
envelope rather than exact equality.
"""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import COAXIndex
from repro.data import make_airline
from repro.engine import BatchQueryExecutor, QueryServer
from repro.obs import (MetricsRegistry, PauseWatchdog, Tracer,
                       parse_text_exposition)
from workloads import rects_for


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Tests toggle the process-global tracer; always restore 'off'."""
    yield
    obs.disable_tracing()


# ===================================================================== #
# MetricsRegistry
# ===================================================================== #
def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests", ("plane", "shard"))
    c.inc(plane="read", shard="0")
    c.inc(3, plane="read", shard="1")
    c.inc(plane="write", shard="0")
    assert c.value(plane="read", shard="0") == 1
    assert c.value(plane="read", shard="1") == 3
    assert c.value(plane="write", shard="1") == 0   # never touched
    assert c.total() == 5
    # get-or-create returns the SAME family; a conflicting re-declaration
    # is a programming error, not a silent second family
    assert reg.counter("requests_total", "requests",
                       ("plane", "shard")) is c
    with pytest.raises(ValueError):
        reg.counter("requests_total", "requests", ("other",))
    with pytest.raises(ValueError):
        reg.gauge("requests_total", "now a gauge?")


def test_gauge_set_add():
    reg = MetricsRegistry()
    g = reg.gauge("resident_bytes", "bytes", ("plane",))
    g.set(100, plane="cache")
    g.add(-25, plane="cache")
    assert g.value(plane="cache") == 75


def test_histogram_quantiles_against_numpy_oracle():
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-7.0, sigma=2.0, size=4000)
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency")
    for s in samples:
        h.observe(float(s))
    for q in (0.5, 0.9, 0.99):
        want = float(np.quantile(samples, q))
        got = h.quantile(q)
        assert want / 2 <= got <= want * 2, (q, want, got)
    summ = h.summary()
    assert summ["count"] == len(samples)
    assert summ["sum"] == pytest.approx(samples.sum(), rel=1e-9)
    assert summ["max"] == pytest.approx(samples.max())


def test_histogram_labeled_rollup():
    reg = MetricsRegistry()
    h = reg.histogram("stage_seconds", "stages", ("stage",))
    h.observe(1.0, stage="probe")
    h.observe(2.0, stage="filter")
    assert h.summary(stage="probe")["count"] == 1
    assert h.summary()["count"] == 2          # no labels = all-series rollup
    assert h.summary()["sum"] == pytest.approx(3.0)


def test_render_text_round_trips_and_is_stable():
    reg = MetricsRegistry()
    reg.counter("a_total", "as", ("k",)).inc(2, k="x")
    reg.gauge("b_bytes", "bs").set(7)
    reg.histogram("c_seconds", "cs").observe(0.25)
    text = reg.render_text()
    assert text == reg.render_text()          # deterministic rendering
    parsed = parse_text_exposition(text)
    assert parsed["a_total"]["type"] == "counter"
    assert parsed["a_total"]["samples"] == [("a_total", {"k": "x"}, 2.0)]
    assert parsed["b_bytes"]["samples"] == [("b_bytes", {}, 7.0)]
    # histogram renders as a summary family: quantiles + _sum/_count/_max
    c_samples = {s[0]: s[2] for s in parsed["c_seconds"]["samples"]}
    assert c_samples["c_seconds_count"] == 1.0
    assert c_samples["c_seconds_sum"] == pytest.approx(0.25)
    with pytest.raises(ValueError):
        parse_text_exposition("not { an exposition")


def test_registry_reset_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("n_total", "n").inc(5)
    snap = reg.snapshot()
    assert snap["n_total"]["series"][0]["value"] == 5
    reg.reset()
    assert reg.counter("n_total", "n").value() == 0


# ===================================================================== #
# Tracer
# ===================================================================== #
def test_span_nesting_implicit_parent():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    outer, inner = {e["name"]: e for e in tr.events()}.values()
    evs = {e["name"]: e for e in tr.events()}
    assert evs["inner"]["parent"] == evs["outer"]["id"]
    assert evs["outer"]["parent"] is None
    ok, problems = tr.validate()
    assert ok, problems


def test_pipelined_collect_does_not_adopt_next_wave():
    """The §10.2 seam: wave k's collect-side child must parent to wave k,
    not to wave k+1 whose submit is already on the stack."""
    tr = Tracer()
    w1 = tr.start("wave", k=1)
    # wave 2's submit begins while wave 1 is still in flight
    w2 = tr.start("wave", k=2)
    with tr.attach(w2):
        # ... submit-side work of wave 2 would nest here ...
        pass
    # collect side of wave 1 re-attaches wave 1 explicitly
    with tr.attach(w1):
        with tr.span("device.transfer"):
            pass
    tr.finish(w1)
    with tr.attach(w2):
        with tr.span("device.transfer"):
            pass
    tr.finish(w2)
    evs = tr.events()
    transfers = [e for e in evs if e["name"] == "device.transfer"]
    waves = {e["args"]["k"]: e["id"] for e in evs if e["name"] == "wave"}
    assert transfers[0]["parent"] == waves[1]
    assert transfers[1]["parent"] == waves[2]
    ok, problems = tr.validate()
    assert ok, problems


def test_ring_eviction_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 4
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["s6", "s7", "s8", "s9"]


def test_validate_flags_unclosed_and_uncovered():
    tr = Tracer()
    tr.start("dangling")
    ok, problems = tr.validate()
    assert not ok and any("never finished" in p for p in problems)

    tr2 = Tracer()
    with tr2.span("not_a_wave"):
        with tr2.span("device.dispatch"):
            pass
    ok2, problems2 = tr2.validate()
    assert not ok2 and any("not covered" in p for p in problems2)

    tr3 = Tracer()
    with tr3.span("wave", k=0):
        with tr3.span("device.dispatch"):
            pass
    ok3, problems3 = tr3.validate()
    assert ok3, problems3


def test_cross_thread_finish_and_chrome_export(tmp_path):
    tr = Tracer()
    with tr.span("wave") as w:
        bsp = tr.start("compact.build", parent=w)

        def _worker():
            tr.finish(bsp)

        t = threading.Thread(target=_worker)
        t.start()
        t.join()
    evs = {e["name"]: e for e in tr.events()}
    assert evs["compact.build"]["parent"] == evs["wave"]["id"]
    chrome = tr.to_chrome()
    assert all(ev["ph"] == "X" for ev in chrome["traceEvents"])
    path = tmp_path / "trace.jsonl"
    assert tr.dump_jsonl(str(path)) == 2
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert {l["name"] for l in lines} == {"wave", "compact.build"}


# ===================================================================== #
# PauseWatchdog
# ===================================================================== #
def test_watchdog_detects_pause_and_attributes_culprit():
    tr = Tracer()
    reg = MetricsRegistry()
    seen = []
    wd = PauseWatchdog(factor=5.0, window=32, min_samples=4,
                       min_gap_s=1e-4, tracer=tr, registry=reg,
                       callback=lambda g, m, c: seen.append((g, c)))
    t = 0.0
    for _ in range(8):                       # steady 10ms cadence
        wd.wave_done(now=t)
        t += 0.01
    # a background install span sits exactly inside the big gap
    sp = tr.start("compact.install")
    sp.t0 = t + 0.05
    tr.finish(sp)
    sp.t1 = t + 0.45
    rec = wd.wave_done(now=t + 0.5)          # 0.5s gap vs 10ms median
    assert rec is not None
    assert rec["culprit"]["name"] == "compact.install"
    assert reg.counter("serving_pause_total", "", ("culprit",)) \
              .value(culprit="compact.install") == 1
    assert seen and seen[0][1]["name"] == "compact.install"
    assert wd.describe()["last_culprit"] == "compact.install"


def test_watchdog_steady_cadence_never_fires():
    wd = PauseWatchdog(factor=5.0, min_samples=4, registry=MetricsRegistry())
    t = 0.0
    for _ in range(64):
        assert wd.wave_done(now=t) is None
        t += 0.01
    assert wd.pause_count == 0


# ===================================================================== #
# Executor ring + stats delegation (satellite a)
# ===================================================================== #
def test_wave_stats_ring_bounded_but_totals_exact():
    ds = make_airline(4000)
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data)
    ex = BatchQueryExecutor(idx, max_batch=4, wave_history=3)
    want = [idx.query(r) for r in rects]
    got = ex.execute(rects)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    n_waves = -(-len(rects) // 4)
    s = ex.stats()
    assert s["waves"] == n_waves
    assert s["queries"] == len(rects)        # totals survive ring eviction
    assert len(ex.wave_stats) == min(3, n_waves)
    # the ring keeps the TRAILING waves, with their original indices
    assert [w.wave for w in ex.wave_stats] == \
        list(range(n_waves - min(3, n_waves), n_waves))


def test_executor_stats_from_private_registry():
    ds = make_airline(3000)
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data)
    ex = BatchQueryExecutor(idx, max_batch=8)
    ex.execute(rects)
    s = ex.stats()
    assert s["queries"] == len(rects)
    assert ex.metrics.counter("queries").value() == len(rects)
    assert ex.metrics.get("wave_seconds").summary()["count"] == s["waves"]
    # two executors never share counters
    ex2 = BatchQueryExecutor(idx, max_batch=8)
    assert ex2.stats()["queries"] == 0


# ===================================================================== #
# Bit-identity: telemetry on == telemetry off
# ===================================================================== #
def _flat(executor, rects):
    return executor.execute(rects)


def test_tracing_on_off_bit_identity_numpy():
    ds = make_airline(5000)
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data)
    ex = BatchQueryExecutor(idx, max_batch=8, backend="numpy")
    obs.disable_tracing()
    off = _flat(ex, rects)
    tr = obs.enable_tracing()
    on = _flat(ex, rects)
    ok, problems = tr.validate()
    assert ok, problems
    assert all(np.array_equal(a, b) for a, b in zip(on, off))
    assert any(e["name"] == "wave" for e in tr.events())


def test_tracing_on_off_bit_identity_device():
    pytest.importorskip("jax")
    ds = make_airline(5000)
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data)
    ex = BatchQueryExecutor(idx, max_batch=8, backend="device")
    obs.disable_tracing()
    off = _flat(ex, rects)
    tr = obs.enable_tracing()
    on = _flat(ex, rects)
    ok, problems = tr.validate()
    assert ok, problems
    assert all(np.array_equal(a, b) for a, b in zip(on, off))
    # device waves must show their dispatch/transfer split under the wave
    names = {e["name"] for e in tr.events()}
    assert "device.dispatch" in names and "device.transfer" in names


def test_server_drain_span_and_watchdog_wiring():
    ds = make_airline(3000)
    idx = COAXIndex(ds.data)
    rects = rects_for(ds.data)
    srv = QueryServer(idx, max_batch=8)
    tr = obs.enable_tracing()
    for r in rects:
        srv.submit(r)
    srv.drain()
    names = [e["name"] for e in tr.events()]
    assert "server.drain" in names
    s = srv.stats()
    assert "pauses" in s and "pause_median_gap_s" in s


def test_span_without_tracer_is_the_shared_null_context():
    obs.disable_tracing()
    assert obs.span("wave", queries=3) is obs._NULL_CTX
    obs.enable_tracing()
    assert obs.span("wave") is not obs._NULL_CTX
    obs.disable_tracing()
    assert obs.span("device.probe") is obs._NULL_CTX


MIRRORED = ("server.drain", "wave", "device.probe", "device.inputs",
            "device.dispatch", "device.transfer", "device.wait",
            "device.copy")


def test_spans_mirror_into_the_profiler_trace(tmp_path):
    """Under a ``jax.profiler`` capture every program span of a device
    drain also lands on the serving thread's line of the ``/host:`` plane,
    under its own name and lasting as long as the ring says."""
    import jax
    from jax.profiler import ProfileData

    ds = make_airline(3000)
    rects = rects_for(ds.data)
    srv = QueryServer(COAXIndex(ds.data), max_batch=4, backend="device")
    srv.submit_many(rects)
    srv.drain()                                   # compile outside the trace
    tr = obs.enable_tracing()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.submit_many(rects)
        srv.drain()
    finally:
        jax.profiler.stop_trace()
    ring = [e for e in tr.events() if e["name"] in MIRRORED]
    assert {e["name"] for e in ring} == set(MIRRORED)
    assert sum(e["name"] == "wave" for e in ring) > 2    # waves overlapped
    (pb,) = tmp_path.rglob("*.xplane.pb")
    lines = [list(ln.events) for p in ProfileData.from_file(str(pb)).planes
             if p.name.startswith("/host:") for ln in p.lines]
    (serving,) = [evs for evs in lines
                  if any(ev.name == "server.drain" for ev in evs)]
    for name in MIRRORED:
        ann = sorted((ev.start_ns, ev.duration_ns * 1e-9) for ev in serving
                     if ev.name == name)
        rec = sorted((e["t0"], e["t1"] - e["t0"]) for e in ring
                     if e["name"] == name)
        assert len(ann) == len(rec), name
        for (_, a), (_, r) in zip(ann, rec):
            assert abs(a - r) <= max(0.05 * r, 50e-6), (name, a, r)
