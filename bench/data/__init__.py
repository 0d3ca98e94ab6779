"""Data generators, one module per generator name in a configuration's
``data.generator``.  Each module has ``make(seed, **params) -> (X, y)``."""
