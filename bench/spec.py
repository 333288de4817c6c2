"""Finds a cell's configuration, traffic mix and per-layer readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, so a new one is added as a
file plus a ``BENCHMARK.json`` entry, with no edit to this module:

* ``bench/configs/<config>.json`` — the deployment (``BENCHMARK.json``
  names the file);
* ``bench/traffic/<mix>.json`` — the mix's parameters, whose ``pool`` and
  ``arrivals`` kinds name the generator modules ``bench/traffic/<kind>.py``;
* ``bench/tables/<table>.py`` — the device table generator a config names;
* ``bench/metrics/<metric>.py`` — the reader of a per-layer metric; a
  metric ``name.split`` by the end-to-end metric it moves (``x.qps``,
  ``x.p95``) shares the reader ``x.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the BENCHMARK.json metrics this cell reports
    per_layer: List[dict]
    root: Path

    def module(self, kind: str, name: str) -> ModuleType:
        return load_module(self.root, kind, name)


def load_module(root: Path, kind: str, name: str) -> ModuleType:
    """Import ``bench/<kind>/<name>.py`` under ``root`` by its path."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def _reports(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # without a list, an end-to-end metric goes everywhere and a per-layer
    # one wherever the end-to-end metric it moves is reported
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    w = _by_name(bench["workloads"], workload, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    config = json.loads((root / c["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload, [])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer,
                root)


def reader_name(metric: str) -> str:
    """``coax_fused_scan_roofline.qps`` -> ``coax_fused_scan_roofline``."""
    return metric.split(".", 1)[0]


def read_per_layer(cell: Cell, ctx) -> Dict[str, dict]:
    """Run every per-layer reader of the cell; a reader that finds nothing
    to read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.module("metrics", reader_name(m["name"])).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
