"""Chip benchmark of the coadd system: one cell per run, driven by data.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json``.  Configurations, traffic mixes, driver
kinds, per-layer metrics and limits are files found by name under this
directory; the yardstick (archive generator, float64 reference, trace
reduction, work counts, chip peaks) lives here too, apart from the program.
"""
