"""``tiles_read_pct`` reads the work-list counts of the ``device.inputs``
spans, and leaves itself out where the program has none."""
from types import SimpleNamespace as NS

import numpy as np

from bench import spec
from bench.tests.cells import ROOT  # noqa: F401  (puts src/ on the path)

CELLS = {"airline-80m.knn10-closed": "tiles_read_pct.qps",
         "osm-105m.knn10-open": "tiles_read_pct.p50"}


def _read(spans):
    return spec.load_cell("osm-105m.knn10-open").module(
        "metrics", "tiles_read_pct").read(NS(spans=spans))


def test_each_cell_reports_tiles_read_pct():
    for workload, name in CELLS.items():
        m = {m["name"]: m for m in spec.load_cell(workload).per_layer}
        assert m[name]["layer"] == "device plan"
        assert m[name]["source"] == "program_span"


def test_reader_sums_the_wave_counts():
    spans = [{"name": "device.inputs", "args": {"tiles_listed": 3,
                                                "tiles_image": 400}},
             {"name": "device.probe", "args": {}},
             {"name": "device.inputs", "args": {"tiles_listed": 5,
                                                "tiles_image": 400}}]
    assert _read(spans) == 100.0 * 8 / 800
    # a program whose spans carry no tile counts reports nothing
    assert _read([{"name": "device.inputs", "args": {"bytes_h2d": 8}}]) \
        is None
    assert _read([]) is None


def test_reader_on_a_served_wave():
    """The interpret-mode kernel of a real plan: a few narrow rects read a
    small share of the image's tiles."""
    from repro import obs
    from repro.core import COAXIndex
    from repro.data import knn_rect_queries, make_osm

    ds = make_osm(300_000, seed=2)
    idx = COAXIndex(ds.data, backend="device",
                    device_opts={"use_pallas": True, "interpret": True})
    rects = knn_rect_queries(ds.data, 4, 10, seed=1)
    tr = obs.enable_tracing()
    try:
        idx.query_batch(rects)
    finally:
        obs.disable_tracing()
    pct = _read(tr.events())
    assert pct is not None and 0 < pct < 50, pct
    assert np.isfinite(pct)
