"""Chip benchmark of the adaptive fastest-k system: one cell, one run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
(see ``bench/run.py``).  Everything here is found by name: a cell is an entry
of ``BENCHMARK.json``; its configuration is ``bench/configs/<config>.json``;
its traffic mix, which names the entry kind that drives it, is
``bench/traffic/<traffic>.json``; the entry kind is ``bench/drivers/<kind>.py``;
each per-layer metric is ``bench/metrics/<name>.py``.
"""
