"""The serving benchmark: a wall-clock open loop through ``ServingEngine``.

``bench/run.py`` is the entry point. A cell is found by the names in the
repository's ``BENCHMARK.json``: its configuration file, its traffic mix
``bench/traffic/<mix>.json`` and one reader ``bench/metrics/<metric>.py``
per metric.
"""
