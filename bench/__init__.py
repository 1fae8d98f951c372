"""Chip benchmark of the serving stack: one cell, one run, one JSON line.

Run from the root of a checkout:

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root lists the cells, their configurations and
traffic mixes, and the metrics each cell reports.  Everything that belongs
to one configuration, traffic mix or metric is a file of its own under
``bench/`` that the harness finds by name.
"""
