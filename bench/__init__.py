"""Chip benchmark of the HEAT reproduction: one cell (configuration x traffic
mix) per run, driven by ``BENCHMARK.json`` and the data files in this
directory.  Entry point: ``python bench/run.py --workload <cell> ...``."""
