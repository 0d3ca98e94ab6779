"""The spam cell (``spam-fit``) at a size a CPU test run can hold: a sound
run is correct against the plain reference, the control is not, and the
Gram kernel's seconds per fit are read from the trace.

Cut for the CPU: 1,000 rows and n = 8 (a degree-2 border of 36, Lcap 64)
in place of 4,601 rows and n = 57, and two row orders in place of four.
At the full width one fit takes minutes on the CPU (a border of 1,653 a
class through the candidate loop at Lcap 2048, an SVM on about 3,300
features).  The tiled Gram kernel at the full width is covered by
``tests/test_kernels.py`` (interpret mode, several tiles) and
``tests/test_chip_compile.py`` (n = 57, L = K = 2048 for a v5e)."""

import time

import numpy as np
import pytest

from bench import control, deploy, harness
from bench.harness import Run
from bench.tests.small import rebuilt_program

CELL = "spam-fit"
SMALL = {"data": {"m": 1000, "n": 8}, "traffic": {"order_seeds": [1, 2]}}
SEED = 20260917


def test_spam_cell_is_declared():
    bench = harness.benchmark()
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "spam-cgavi_ihb", "fit_repeat_4orders", 1)
    config = deploy.load_config(cell["config"])
    assert (config["data"]["m"], config["data"]["n"], config["reduced"]) == (4601, 57, [])


def _run():
    return harness.run_cell(CELL, SEED, 1.0, False, time.perf_counter(),
                            require_chip=False, overrides=SMALL)


def test_small_spam_run_is_correct():
    result = _run()
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"setup_s", "fit_s"}


def test_small_spam_control_is_not_correct():
    values = control.readings([CELL], SEED, SMALL)[CELL]
    limits = deploy.load_json(deploy.BENCH / "limits" / f"{CELL}.json")["checks"]
    assert [n for n in limits if n in values and values[n] > limits[n]["limit"]], values


def test_small_spam_svm_on_half_the_rows_is_not_correct(monkeypatch):
    """The planted fault that bounds ``svm_excess`` (the control reads only
    about twice the sound runs there): the SVM head fitted to the first half
    of its rows."""
    deploy.use_program()
    from repro.core import svm

    whole = svm.LinearSVM.fit

    def half(self, X, y):
        h = np.asarray(X).shape[0] // 2
        return whole(self, np.asarray(X)[:h], np.asarray(y)[:h])

    monkeypatch.setattr(svm.LinearSVM, "fit", half)
    with rebuilt_program():
        result = _run()
    check = result["checks"]["svm_excess"]
    assert result["correct"] is False and check["value"] > check["limit"], result["checks"]


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_seconds(self, kernel):
        return self.seconds if kernel == "gram_update_acc" else None


@pytest.mark.parametrize("seconds,fits,expect", [
    (0.006, 3, 0.002),
    (None, 3, None),  # the trace names no Gram op
    (0.006, 0, None),  # no fit in the window
])
def test_gram_seconds_per_fit(seconds, fits, expect):
    run = Run({"fits": [{}] * fits}, _Trace(seconds), {})
    got = deploy.load_module("metrics", "fit.gram_s").read(run)
    assert got == (None if expect is None else pytest.approx(expect))
