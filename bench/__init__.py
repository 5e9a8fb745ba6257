"""Chip benchmark of FFTB-JAX, driven by data.

``BENCHMARK.json`` at the repository root names the cells; each cell's
configuration, traffic mix, per-layer metrics and correctness limits are
files under this directory, found by name (see ``registry.py``).  One run
of one cell: ``python bench/run.py --workload NAME --seed N --seconds S
--trace 0|1``.
"""
