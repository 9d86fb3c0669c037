"""Chip benchmark: one cell per process, driven by ``BENCHMARK.json``.

Configurations (``configs/``), traffic mixes (``traffic/``), drivers
(``drivers/``) and per-layer metric readers (``metrics/``) are found by the
names that ``BENCHMARK.json`` gives them.  See ``run.py``.
"""
