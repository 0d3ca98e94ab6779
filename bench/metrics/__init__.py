"""Per-layer metrics, one module per metric name in ``BENCHMARK.json``.
Each has ``read(run) -> float | None``: ``run.stats`` (the window's driver
and program counters), ``run.trace`` (the reduced trace), ``run.peaks``.
A reader that finds nothing to read returns None."""
