"""Chip benchmark of the vanishing-ideal Algorithm 2 fit.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything a cell needs is found
by name: its deployment in ``configs/``, its traffic mix in ``traffic/``, the
driver the mix names in ``drivers/``, its per-layer metrics in ``metrics/``
and its correctness limits in ``limits/``.
"""
