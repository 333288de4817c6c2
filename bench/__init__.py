"""Chip benchmark of the COAX query server (``BENCHMARK.json``, ``PERF.md``).

``bench/run.py`` runs one cell: a deployment from ``bench/configs`` under a
traffic mix from ``bench/traffic``, served through ``engine.QueryServer``
on one TPU, checked against a plain full-scan reference.
"""
