"""A cell is added as data: a new configuration, traffic mix, driver,
per-layer metric and limits are new files plus ``BENCHMARK.json`` entries,
found by name, with no edit to a file that is already there."""

import hashlib
import json
import shutil
import time

import pytest

from bench import deploy, harness

DRIVER = '''
def setup(ctx):
    return {"per_call": ctx.traffic["per_call"], "size": ctx.config["data"]["size"]}


def window(state, seconds):
    return {"attempted": 3, "failed": 0, "e2e": {"dummy_ops_per_s": 3.0 * state["per_call"]},
            "stats": {"calls": 3, "size": state["size"]}}


def check(state, win):
    return {"dummy_gap": 0.0}
'''

METRIC = '''
def read(run):
    return run.stats["calls"] * run.stats["size"]
'''


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with one more cell added as new files."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(deploy.BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((deploy.ROOT / "BENCHMARK.json").read_text())
    before = digest(bench)
    (bench / "configs" / "dummy-deploy.json").write_text(json.dumps(
        {"name": "dummy-deploy", "data": {"size": 7}, "reduced": []}))
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"driver": "dummy_driver", "per_call": 2}))
    (bench / "drivers" / "dummy_driver.py").write_text(DRIVER)
    (bench / "metrics" / "dummy.calls.py").write_text(METRIC)
    (bench / "limits" / "dummy-cell.json").write_text(json.dumps(
        {"checks": {"dummy_gap": {"limit": 0}}}))
    spec["configs"].append({"name": "dummy-deploy", "source": "https://example.org/dummy",
                            "file": "bench/configs/dummy-deploy.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "dummy-cell", "config": "dummy-deploy",
                              "traffic": "dummy_mix", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "dummy_ops_per_s", "unit": "ops/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["dummy-cell"]})
    spec["per_layer"].append({"name": "dummy.calls", "unit": "calls", "better": "higher",
                              "source": "program_counter", "layer": "dummy",
                              "moves": "dummy_ops_per_s", "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(deploy, "BENCH", bench)
    monkeypatch.setattr(deploy, "ROOT", root)
    yield bench
    after = digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_new_cell_runs_from_new_files_alone(checkout):
    result = harness.run_cell("dummy-cell", 5, 1.0, False, time.perf_counter(),
                              require_chip=False)
    assert result["correct"] is True
    assert result["attempted"] == 3 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "dummy_ops_per_s"}
    assert result["metrics"]["dummy_ops_per_s"] == {"value": 6.0, "unit": "ops/s"}
    assert list(result)[-1] == "checks"
    assert result["checks"] == {"dummy_gap": {"value": 0.0, "limit": 0.0}}


def test_new_per_layer_metric_is_found_by_name(checkout, monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {})  # the CPU has no peaks
    result = harness.run_cell("dummy-cell", 5, 1.0, True, time.perf_counter(),
                              require_chip=False)
    assert result["metrics"] == {"dummy.calls": {"value": 21.0, "unit": "calls"}}
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
