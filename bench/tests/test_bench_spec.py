"""A configuration, a traffic mix and a per-layer metric added as new files
(plus their ``BENCHMARK.json`` entries) are found by name, with no edit to
a file the benchmark already has."""
import json
import shutil
from types import SimpleNamespace as NS

from bench import spec
from bench.tests.cells import ROOT


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "osm-105m.json").read_text())
    (b / "configs" / "osm-10m.json").write_text(
        json.dumps(dict(cfg, name="osm-10m", n_rows=10_000_000)))
    (b / "traffic" / "knn100-open.json").write_text(json.dumps({
        "pool": {"kind": "knn_rect", "k": 100, "size": 8192},
        "arrivals": {"kind": "open_loop", "rate_qps": 5},
        "warm_batches": [4, 8]}))
    (b / "metrics" / "waves_per_s.py").write_text(
        "def read(ctx):\n    return ctx.served['waves'] / ctx.window_s\n")
    bench["configs"].append({"name": "osm-10m", "source": "x",
                             "file": "bench/configs/osm-10m.json",
                             "reduced": ["n_rows"], "why": "x"})
    bench["workloads"].append({"name": "osm-10m.knn100-open",
                               "config": "osm-10m", "traffic": "knn100-open",
                               "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "query_p50_ms":
            m["workloads"].append("osm-10m.knn100-open")
    bench["per_layer"].append({"name": "waves_per_s.p50", "unit": "1/s",
                               "better": "higher", "source": "program_counter",
                               "layer": "server and executor",
                               "moves": "query_p50_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("osm-10m.knn100-open", tmp_path)
    assert cell.config["n_rows"] == 10_000_000 and cell.chips == 1
    assert cell.traffic["pool"]["k"] == 100
    assert cell.module("traffic", cell.traffic["arrivals"]["kind"]).run
    assert cell.module("tables", cell.config["table"]).make
    assert [m["name"] for m in cell.end_to_end] == [
        "query_p50_ms", "hbm_bytes_per_row", "setup_s"]
    # without a workloads list the new metric goes where query_p50_ms is
    assert [m["name"] for m in cell.per_layer] == ["waves_per_s.p50"]
    assert "waves_per_s.p50" in [
        m["name"] for m in spec.load_cell("osm-105m.knn10-open",
                                          tmp_path).per_layer]
    got = spec.read_per_layer(cell, NS(served={"waves": 30}, window_s=10.0))
    assert got == {"waves_per_s.p50": {"value": 3.0, "unit": "1/s"}}


def test_every_cell_reports_setup_another_metric_and_a_layer():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert (ROOT / "bench" / "metrics"
                    / f"{spec.reader_name(m['name'])}.py").is_file()


def test_reader_that_finds_nothing_leaves_its_metric_out():
    cell = spec.load_cell("airline-80m.knn10-closed")
    ctx = NS(trace=None, spans=[], needed_bytes=None, peaks={},
             served={"queries": 0, "waves": 0, "hit_overflows": 0})
    assert spec.read_per_layer(cell, ctx) == {}


def test_every_end_to_end_bound_is_a_share_up_to_a_quarter():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.25, m["name"]
