"""The on-chip benchmark: see ``run.py`` and ``BENCHMARK.json``."""
