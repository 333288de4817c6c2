"""A traced run on the CPU reads the program's host stages from its spans."""
import jax
import pytest

from bench import run
from bench.tests.cells import PEAKS, SEED, tiny_cell

SPAN_METRICS = {
    "airline-80m.knn10-closed": {"probe_ms.qps", "inputs_ms.qps",
                                 "copy_ms.qps"},
    "osm-105m.knn10-open": {"probe_ms.p50", "inputs_ms.p50", "copy_ms.p50",
                            "queue_wait_ms.p50"},
}


@pytest.mark.parametrize("workload", sorted(SPAN_METRICS))
def test_traced_run_reports_the_span_metrics(workload):
    cell = tiny_cell(workload)
    names = SPAN_METRICS[workload]
    assert names <= {m["name"] for m in cell.per_layer}
    out = run.run_cell(cell, SEED, 0.3, True, PEAKS, jax.devices(),
                       log=lambda **kv: None, kernel_check=False)
    assert out["correct"]
    m = out["metrics"]
    assert names <= set(m)
    assert all(m[k]["value"] > 0 and m[k]["unit"] == "ms" for k in names)


@pytest.mark.parametrize("traced, window", [(True, 0.2), (False, 0.4)],
                         ids=["traced", "untraced"])
def test_only_a_traced_window_is_cut_to_trace_seconds(monkeypatch, traced,
                                                      window):
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.2)
    logs = []
    out = run.run_cell(tiny_cell("airline-80m.knn10-closed"), SEED, 0.4,
                       traced, PEAKS, jax.devices(),
                       log=lambda **kv: logs.append(kv), kernel_check=False)
    assert out["correct"]
    win = next(kv for kv in logs if kv["stage"] == "window")
    assert win["seconds"] == window and win["elapsed_s"] >= window
    assert (win["elapsed_s"] < 0.4) == traced
