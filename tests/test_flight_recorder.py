"""Flight-recorder stack tests: device capture, SLO burn rates, the perf
baseline, the bench-history gate, and the report/aggregation plumbing.

Covers the contracts behind the device-level observability layer and the
regression gate:

* ``CompileWindow`` attributes real XLA backend-compile seconds to a region;
* ``sample_memory`` feeds stats peaks and registry gauges from one sample;
* fit stats carry the compile accounting (``compile_seconds`` /
  ``xla_compiles``) and no HLO flop estimate;
* ``SLOMonitor`` fires when BOTH burn windows exceed the threshold and
  stops as soon as the short window drains;
* ``baseline.load_history`` tolerates a torn tail but refuses mid-file
  corruption; ``check_regression`` passes an unchanged tree and fails an
  injected 2x slowdown (metric and sketch bands);
* ``benchmarks.history`` flattens bench docs deterministically and
  ``run_gate`` applies the noise-floor and ``BENCH_SOFT`` escapes;
* ``obs_report`` keeps rendering over torn metric tails and emits
  machine-readable JSON;
* ``merge_traces`` produces a Perfetto-valid document with per-process
  tracks and harness markers (the chaos-export shape);
* solver-discipline stats survive ``api.aggregate_fit_stats`` into the
  classifier-level view and the metric registry.
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import history as bench_history
from repro import api, obs
from repro.obs import baseline, device, slo
from repro.obs.metrics import Histogram, Registry
from repro.launch import obs_report


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Enabled, unsampled, empty recorder state; no soft-fail env leakage."""
    monkeypatch.delenv("BENCH_SOFT", raising=False)
    monkeypatch.delenv("OBS_DEVICE", raising=False)
    obs.configure(enabled=True, sample_every=1)
    obs.reset()
    yield
    obs.configure(enabled=True, sample_every=1)
    obs.reset()


# ---------------------------------------------------------------------------
# device: compile windows, memory sampling, fit-stats contract


def test_compile_window_attributes_backend_compile():
    if not device._ensure_listener():
        pytest.skip("jax monitoring channel unavailable")
    fn = jax.jit(lambda a: jnp.sin(a) + jnp.cos(a))
    x = jnp.linspace(0.0, 1.0, 37)
    with device.CompileWindow() as cw:
        fn(x).block_until_ready()
    assert cw.count >= 1
    assert cw.seconds > 0.0
    with device.CompileWindow() as warm:
        fn(x).block_until_ready()
    assert warm.count == 0
    assert warm.seconds == 0.0


def test_sample_memory_updates_stats_and_gauges():
    keep = jnp.ones((64, 64), dtype=jnp.float32)
    keep.block_until_ready()
    stats = {}
    out = device.sample_memory(stats)
    assert out.get("live_bytes", 0) >= keep.nbytes
    assert stats["live_bytes_peak"] >= keep.nbytes
    snap = {r["name"] for r in obs.registry().snapshot()}
    assert "device.live_bytes" in snap
    assert "device.live_bytes_peak" in snap
    # peaks are monotone: a smaller later sample never lowers them
    peak = stats["live_bytes_peak"]
    device.sample_memory(stats)
    assert stats["live_bytes_peak"] >= peak


def test_fit_stats_carry_device_fields():
    rng = np.random.default_rng(0)
    X = rng.uniform(0.0, 1.0, (120, 3))
    model = api.fit(X, method="oavi", psi=0.1, max_degree=2)
    assert model.stats["compile_seconds"] >= 0.0
    assert model.stats["xla_compiles"] >= 0
    # the HLO flop estimate (blind inside Pallas calls) is gone from fits
    assert not [k for k in model.stats if "flop" in k]


def test_profile_window_noop_without_env(monkeypatch):
    monkeypatch.delenv("OBS_JAX_PROFILE", raising=False)
    w = device.profile_window("test")
    assert w is device._NOOP_WINDOW
    with w:
        pass  # no profiler started, no events emitted
    assert not [e for e in obs.trace_events()
                if e.get("name") == "device/profile_start"]


# ---------------------------------------------------------------------------
# SLO: burn-rate windows over the registry


def _slo_windows():
    return (slo.BurnWindow(long_s=60.0, short_s=5.0, max_burn=10.0),)


def test_error_objective_alerts_and_recovers():
    reg = Registry()
    bad = reg.counter("loop.update_failures")
    total = reg.counter("loop.updates_total")
    mon = slo.SLOMonitor(
        [slo.error_objective("errs", "loop.update_failures",
                             "loop.updates_total", budget_frac=0.01)],
        windows=_slo_windows(), registry=reg, now=lambda: 0.0,
    )
    assert mon.tick(now=0.0) == []
    for _ in range(100):
        total.inc()
    for _ in range(50):
        bad.inc()
    alerts = mon.tick(now=1.0)
    assert len(alerts) == 1
    assert alerts[0]["objective"] == "errs"
    assert alerts[0]["burn"] >= 10.0
    assert mon.alerting()
    # healthy traffic drains the short window -> alert clears even though
    # the long window still remembers the incident
    for _ in range(400):
        total.inc()
    assert mon.tick(now=10.0) == []
    assert not mon.alerting()
    state = mon.state()
    assert state["ticks"] == 3
    json.dumps(state)  # slo.json must serialize


def test_latency_objective_counts_samples_above_threshold():
    reg = Registry()
    h = reg.histogram("serve.seconds", backend="local")
    mon = slo.SLOMonitor(
        [slo.latency_objective("lat", "serve.seconds", threshold_s=0.1,
                               budget_frac=0.01, backend="local")],
        windows=_slo_windows(), registry=reg, now=lambda: 0.0,
    )
    mon.tick(now=0.0)  # baseline snapshot: burn rates need a delta
    for _ in range(90):
        h.observe(0.001)
    for _ in range(10):
        h.observe(0.5)  # 10% above threshold vs a 1% budget
    assert mon.tick(now=1.0)
    assert mon.alerting()
    obj = mon.state()["objectives"][0]
    assert obj["total"] == 100
    assert obj["bad"] == 10


def test_slo_requires_valid_budget():
    with pytest.raises(ValueError):
        slo.latency_objective("x", "m", threshold_s=0.1, budget_frac=0.0)
    with pytest.raises(ValueError):
        slo.error_objective("x", "b", "t", budget_frac=1.0)
    with pytest.raises(ValueError):
        slo.SLOMonitor([])


# ---------------------------------------------------------------------------
# baseline: history parsing + the regression decision


def _record(metrics=None, sketches=None):
    return {"schema": baseline.RECORD_SCHEMA,
            "metrics": metrics or {}, "sketches": sketches or {}}


def test_load_history_tolerates_torn_tail(tmp_path):
    p = tmp_path / "history.jsonl"
    p.write_text(json.dumps(_record({"a:t_s": 1.0})) + "\n"
                 + json.dumps(_record({"a:t_s": 1.1})) + "\n"
                 + '{"schema": "bench-history.v1", "metr')
    records, warnings = baseline.load_history(str(p))
    assert len(records) == 2
    assert any("torn tail" in w for w in warnings)


def test_load_history_raises_on_midfile_corruption(tmp_path):
    p = tmp_path / "history.jsonl"
    p.write_text('{"not json\n' + json.dumps(_record()) + "\n")
    with pytest.raises(ValueError, match="mid-file"):
        baseline.load_history(str(p))


def test_load_history_skips_foreign_schema(tmp_path):
    p = tmp_path / "history.jsonl"
    p.write_text(json.dumps({"schema": "bench-history.v99"}) + "\n"
                 + json.dumps(_record({"a:t_s": 1.0})) + "\n")
    records, warnings = baseline.load_history(str(p))
    assert len(records) == 1
    assert any("schema" in w for w in warnings)
    missing, warnings = baseline.load_history(str(tmp_path / "nope.jsonl"))
    assert missing == [] and warnings


def test_is_time_metric_recognizes_duration_leaves():
    assert baseline.is_time_metric("fit.quick/rows/0:t_fit_s")
    assert baseline.is_time_metric("obs/device/1:mean_capture_ms")
    assert baseline.is_time_metric("x/y/0:seconds")
    assert not baseline.is_time_metric("fit.quick/rows/0:flops")
    assert not baseline.is_time_metric("serve/rows/0:bytes")


def test_check_regression_passes_unchanged_and_fails_2x():
    key = "fit.quick/rows/0:t_fit_s"
    base = [_record({key: 1.0}), _record({key: 1.05})]
    ok = baseline.check_regression(_record({key: 1.02}), base)
    assert ok["status"] == "pass"
    assert ok["checked"] == 1 and not ok["findings"]
    bad = baseline.check_regression(_record({key: 2.0}), base)
    assert bad["status"] == "fail"
    (finding,) = bad["findings"]
    assert finding["kind"] == "metric" and finding["key"] == key
    assert finding["ratio"] == pytest.approx(2.0)
    assert finding["current"] > finding["allowed"]


def test_check_regression_spread_widens_allowance():
    key = "a/b/0:t_s"
    wobbly = [_record({key: 1.0}), _record({key: 1.6})]
    # 1.5x is over the flat 25% tolerance but inside the observed 1.6x
    # spread (times its margin) — a historically noisy metric must not flap
    verdict = baseline.check_regression(_record({key: 1.5}), wobbly)
    assert verdict["status"] == "pass"


def test_check_regression_skips_fast_and_thin_metrics():
    fast = "a/b/0:t_s"
    thin = "c/d/0:t_s"
    count = "a/b/0:rows"
    base = [_record({fast: 1e-4, count: 50.0}),
            _record({fast: 1e-4, count: 50.0})]
    base[0]["metrics"][thin] = 1.0  # only one history point
    verdict = baseline.check_regression(
        _record({fast: 1.0, thin: 9.9, count: 5000.0}), base)
    assert verdict["status"] == "insufficient"
    assert verdict["checked"] == 0
    assert any("timing floor" in s for s in verdict["skipped"])
    assert any("history point" in s for s in verdict["skipped"])


def test_check_regression_sketch_band():
    def sketch(scale):
        h = Histogram()
        for i in range(200):
            h.observe(scale * (0.05 + 0.001 * (i % 10)))
        return h.to_state()

    series = "serve.transform_seconds{backend=local}"
    base = [_record(sketches={series: sketch(1.0)}),
            _record(sketches={series: sketch(1.0)})]
    ok = baseline.check_regression(_record(sketches={series: sketch(1.02)}), base)
    assert ok["status"] == "pass"
    bad = baseline.check_regression(_record(sketches={series: sketch(2.0)}), base)
    assert bad["status"] == "fail"
    assert bad["findings"][0]["kind"] == "sketch"
    assert bad["findings"][0]["key"] == series


def test_merge_sketches_is_exact():
    h1, h2 = Histogram(), Histogram()
    for v in (0.01, 0.02, 0.04):
        h1.observe(v)
    for v in (0.08, 0.16):
        h2.observe(v)
    merged = baseline.merge_sketches(
        [_record(sketches={"s": h1.to_state()}),
         _record(sketches={"s": h2.to_state()}), _record()], "s")
    assert merged.count == 5
    assert merged.sum == pytest.approx(h1.sum + h2.sum)
    assert baseline.merge_sketches([_record()], "s") is None


# ---------------------------------------------------------------------------
# benchmarks.history: flattening, record collection, the gate CLI


def test_flatten_bench_keys_are_deterministic():
    doc = {"bench": "fit", "meta": {"quick": True},
           "rows": [{"section": "rows", "t_fit_s": 1.5, "m": 100,
                     "ok": True, "label": "x"},
                    {"section": "rows", "t_fit_s": 2.5, "m": 200}]}
    flat = bench_history.flatten_bench(doc)
    assert flat == {"fit.quick/rows/0:t_fit_s": 1.5,
                    "fit.quick/rows/0:m": 100.0,
                    "fit.quick/rows/1:t_fit_s": 2.5,
                    "fit.quick/rows/1:m": 200.0}
    doc["meta"]["quick"] = False
    assert all(k.startswith("fit.full/")
               for k in bench_history.flatten_bench(doc))


def test_collect_and_append_record_roundtrip(tmp_path):
    doc = {"bench": "fit", "schema": "bench.v1", "created_unix": 1.0,
           "meta": {"quick": True},
           "rows": [{"section": "rows", "t_fit_s": 1.0}]}
    (tmp_path / "BENCH_fit.json").write_text(json.dumps(doc))
    (tmp_path / "BENCH_torn.json").write_text('{"bench": "to')  # ignored
    obs.registry().histogram("fit.seconds", backend="t").observe(0.25)
    rec = bench_history.collect_record(str(tmp_path))
    assert rec["schema"] == baseline.RECORD_SCHEMA
    assert rec["benches"] == {
        "fit": {"created_unix": 1.0, "rows": 1, "meta": {"quick": True}}}
    assert rec["metrics"]["fit.quick/rows/0:t_fit_s"] == 1.0
    assert "fit.seconds{backend=t}" in rec["sketches"]
    assert rec["env"]["python"]
    path = tmp_path / "history.jsonl"
    bench_history.append_record(rec, str(path))
    bench_history.append_record(rec, str(path))
    records, warnings = baseline.load_history(str(path))
    assert len(records) == 2 and not warnings
    assert records[0]["metrics"] == rec["metrics"]


def _write_history(tmp_path, values):
    key = "fit.quick/rows/0:t_fit_s"
    path = tmp_path / "history.jsonl"
    with open(path, "w") as f:
        for v in values:
            f.write(json.dumps(_record({key: v})) + "\n")
    return str(path)


def test_run_gate_fails_injected_2x_slowdown(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(bench_history, "measure_noise_floor", lambda: 0.0)
    good = _write_history(tmp_path, [1.0, 1.05, 1.02])
    assert bench_history.run_gate(good) == 0
    slow = _write_history(tmp_path, [1.0, 1.05, 2.0])
    assert bench_history.run_gate(slow) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "FAILED" in out


def test_run_gate_escapes(tmp_path, monkeypatch, capsys):
    slow = _write_history(tmp_path, [1.0, 1.05, 2.0])
    # escape 1: the machine's noise floor cannot resolve the tolerance
    monkeypatch.setattr(bench_history, "measure_noise_floor", lambda: 0.5)
    assert bench_history.run_gate(slow) == 0
    assert "cannot resolve" in capsys.readouterr().out
    # escape 2: BENCH_SOFT downgrades the failure on constrained CI
    monkeypatch.setattr(bench_history, "measure_noise_floor", lambda: 0.0)
    monkeypatch.setenv("BENCH_SOFT", "1")
    assert bench_history.run_gate(slow) == 0
    assert "BENCH_SOFT" in capsys.readouterr().out


def test_run_gate_vacuous_pass_below_two_records(tmp_path, capsys):
    assert bench_history.run_gate(_write_history(tmp_path, [1.0])) == 0
    assert "vacuous" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# obs_report: torn-tail tolerance + machine-readable output


def test_report_tolerates_torn_metrics_tail(tmp_path):
    p = tmp_path / "metrics.jsonl"
    p.write_text(json.dumps({"name": "a", "type": "counter", "value": 1}) + "\n"
                 + '{"name": "b", "ty')
    rows, warnings = obs_report.load_metric_rows(str(p))
    assert [r["name"] for r in rows] == ["a"]
    assert any("torn tail" in w for w in warnings)


def test_report_raises_on_midfile_metrics_corruption(tmp_path):
    p = tmp_path / "metrics.jsonl"
    p.write_text('{"broken\n'
                 + json.dumps({"name": "a", "type": "counter", "value": 1})
                 + "\n")
    with pytest.raises(ValueError, match="corrupt"):
        obs_report.load_metric_rows(str(p))


def test_report_json_format(tmp_path, capsys):
    d = tmp_path / "obs"
    d.mkdir()
    (d / "metrics.jsonl").write_text(
        json.dumps({"name": "loop.updates_total", "labels": {},
                    "type": "counter", "value": 3}) + "\n")
    (d / "slo.json").write_text(json.dumps(
        {"objectives": [], "alerting": False, "ticks": 4, "t": 1.0}))
    obs.registry().counter("x").inc()
    with obs.span("work"):
        pass
    obs.export_trace(str(d / "trace.json"))
    obs_report.main(["--obs-dir", str(d), "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert payload["slo"]["ticks"] == 4
    assert payload["metrics"][0]["name"] == "loop.updates_total"
    assert payload["trace"]["events"] >= 1
    # absent slo.json (or a torn mid-replace read) degrades to None
    assert obs_report.load_slo(str(d / "missing.json")) is None
    (d / "torn.json").write_text('{"alert')
    assert obs_report.load_slo(str(d / "torn.json")) is None


# ---------------------------------------------------------------------------
# metrics: empty-sketch None semantics + sketch state round-trips


def test_empty_histogram_quantile_is_none():
    h = Histogram()
    assert h.quantile(0.99) is None
    assert h.count_above(0.0) == 0
    s = h.summary()
    assert s["count"] == 0 and s["sum"] == 0.0
    h.observe(0.5)
    assert h.quantile(0.99) is not None


def test_histogram_state_roundtrip_exact():
    h = Histogram()
    for v in (-1.0, 0.0, 0.001, 0.5, 12.0):
        h.observe(v)
    clone = Histogram.from_state(h.to_state())
    assert clone.count == h.count
    assert clone.sum == pytest.approx(h.sum)
    assert clone.min == h.min and clone.max == h.max
    for q in (0.0, 0.5, 0.99, 1.0):
        assert clone.quantile(q) == h.quantile(q)
    empty = Histogram.from_state(Histogram().to_state())
    assert empty.count == 0
    assert empty.min == math.inf and empty.max == -math.inf
    json.dumps(h.to_state())  # history.jsonl must serialize it


def test_merge_with_empty_operand_is_identity():
    h = Histogram()
    for v in (0.01, 0.5):
        h.observe(v)
    before = h.summary()
    h.merge(Histogram())  # empty right operand changes nothing
    assert h.summary() == before
    empty = Histogram()
    empty.merge(h)  # empty left operand adopts the other sketch exactly
    assert empty.summary() == before
    assert Histogram().merge(Histogram()).quantile(0.5) is None


def test_percentile_summary_unknown_and_empty_return_none():
    reg = Registry()
    assert reg.percentile_summary("no.such.metric") is None
    reg.histogram("h", backend="a")  # registered but empty
    assert reg.percentile_summary("h") is None
    reg.histogram("h", backend="a").observe(0.1)
    assert reg.percentile_summary("h", backend="b") is None  # label mismatch
    s = reg.percentile_summary("h", backend="a")
    assert s is not None and s["count"] == 1


def test_count_above_errs_pessimistic_by_one_bucket():
    h = Histogram()
    for _ in range(10):
        h.observe(0.001)
    for _ in range(5):
        h.observe(1.0)
    assert h.count_above(0.1) == 5
    assert h.count_above(2.0) == 0
    # threshold inside a bucket attributes that bucket as above
    assert h.count_above(0.00099) >= 10


# ---------------------------------------------------------------------------
# trace merge: the chaos-export shape (two processes + harness markers)


def test_merge_traces_two_processes_with_markers():
    def doc(pid, name):
        return {"traceEvents": [
            {"name": "process_name", "ph": "M", "ts": 0, "pid": pid,
             "tid": 0, "args": {"name": name}},
            {"name": "update", "ph": "X", "ts": 10.0, "dur": 5.0,
             "pid": pid, "tid": 1, "cat": "obs", "args": {}},
        ]}

    merged = obs.merge_traces(
        [doc(100, "killed"), doc(100, "resumed")],
        markers=[{"name": "chaos/sigkill", "after_doc": 0,
                  "args": {"phase": "update_start#1"}},
                 {"name": "chaos/recovery", "after_doc": 0, "args": {}}])
    obs.validate_chrome_trace(merged)
    spans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    span_pids = {e["pid"] for e in spans}
    assert len(span_pids) == 2  # same-pid docs still get distinct tracks
    markers = {e["name"]: e for e in merged["traceEvents"]
               if e.get("ph") == "i"}
    assert set(markers) == {"chaos/sigkill", "chaos/recovery"}
    for m in markers.values():
        assert m["s"] == "g"
        assert m["pid"] not in span_pids  # harness track, not a controller
    # markers land in the gap between the killed and the resumed doc
    doc1_start = min(e["ts"] for e in spans if e["pid"] != 100)
    doc0_end = max(e["ts"] + e["dur"] for e in spans if e["pid"] == 100)
    for m in markers.values():
        assert doc0_end < m["ts"] < doc1_start


# ---------------------------------------------------------------------------
# api: fit stats survive aggregation into the registry


def test_solver_stats_survive_fit_classes_aggregation():
    rng = np.random.default_rng(0)
    Xs = [rng.normal(size=(40 + 13 * i, 3)) for i in range(3)]
    models = api.fit_classes(Xs, method="oavi:bpcgavi", psi=0.1, max_degree=2)
    for m in models:
        assert "class_batch_padding" in m.stats
    agg = api.aggregate_fit_stats(models)
    assert agg["class_batched"] == len(Xs)
    pad = agg["class_batch_padding"]
    assert pad["dispatched_rows"] >= sum(X.shape[0] for X in Xs)
    assert pad["padded_rows"] == pad["dispatched_rows"] - sum(
        X.shape[0] for X in Xs)
    assert 0.0 <= pad["waste"] < 1.0
    named = {(r["name"], tuple(sorted((r.get("labels") or {}).items())))
             for r in obs.registry().snapshot()}
    assert ("fit.class_batch_padding_waste", ()) in named
    # group dedup: per-class padding is counted once per batch group
    doubled = api.aggregate_fit_stats(list(models) + list(models))
    assert doubled["class_batch_padding"] == pad
