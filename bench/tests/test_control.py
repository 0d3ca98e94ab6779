"""The control -- the plain reference at three bf16 passes, in the
program's place -- comes out not correct under the limits of every cell of
``BENCHMARK.json``, at a size a test run can hold (on the chip it is read at
the cells' own size)."""

import pytest

from bench import control, deploy, harness
from bench.tests.small import SMALL


@pytest.fixture(scope="module", params=["appendix_c-fit", "credit-fit"])
def cell(request):
    assert request.param in {w["name"] for w in harness.benchmark()["workloads"]}
    return request.param


def test_control_is_not_correct(cell):
    values = control.readings([cell], 20260917, SMALL[cell])[cell]
    limits = deploy.load_json(deploy.BENCH / "limits" / f"{cell}.json")["checks"]
    failed = [n for n in limits if n in values and values[n] > limits[n]["limit"]]
    assert failed, values
