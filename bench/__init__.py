"""Chip benchmark of the Astraea round engine.

Everything a cell needs is found by name: ``BENCHMARK.json`` at the root
lists the cells; each names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); each per-layer metric
has a reader ``bench/metrics/<name>.py``; each cell's correctness limits
sit in ``bench/limits/<cell>.json``. Run one cell with::

    python bench/run.py --workload emnist-ltrf.astraea --seed 7 --seconds 10 --trace 0
"""
