"""Chip benchmark of the BigDAWG polystore: one cell per run, found by
name in ``BENCHMARK.json``.  See ``run.py``."""
