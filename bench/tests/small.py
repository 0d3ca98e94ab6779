"""Cells at a size a CPU test run can hold, and a program whose compiled
steps are rebuilt around a planted fault."""

import contextlib
import time

from bench import deploy, harness

SMALL = {
    "appendix_c-fit": {"data": {"m": 60000}},
    # a draw whose small fit is quick on the CPU (the cell's own draw at
    # 6,000 rows takes minutes there)
    "credit-fit": {"data": {"m": 6000, "seed": 20260917},
                   "traffic": {"order_seeds": [1, 2]}},
}
SECONDS = 1.0


def run(cell: str, seed: int = 20260917):
    return harness.run_cell(cell, seed, SECONDS, False, time.perf_counter(),
                            require_chip=False, overrides=SMALL[cell])


@contextlib.contextmanager
def rebuilt_program():
    """Drop the program's compiled degree steps before and after, so that a
    fault planted in what they call is traced into them, and only there."""
    import jax

    deploy.use_program()
    from repro.core import oavi

    oavi._DEGREE_STEP_CACHE.clear()
    jax.clear_caches()
    try:
        yield
    finally:
        oavi._DEGREE_STEP_CACHE.clear()
        jax.clear_caches()
