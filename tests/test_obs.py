"""Observability subsystem tests: spans, sketch fidelity, trace schema,
no-op guarantees, the fit timing contract, and journal compaction.

These are the regression tests behind the obs contracts stated in
``src/repro/obs`` and ``benchmarks/bench_obs.py``:

* spans nest per-thread and never leak across threads;
* the log-bucket histogram recovers quantiles to within one bucket and
  merges associatively;
* exported traces validate against the Chrome trace-event schema;
* ``obs.disabled()`` makes spans/events true no-ops;
* enabling obs never changes what a fit computes (bit-identity);
* every fit loop reports the same timing contract
  (``time_total == time_setup + time_degrees + time_finalize +
  time_unattributed``);
* ``Journal.compact`` preserves exactly the records the continuous loop's
  resume path needs.
"""

import json
import math
import os
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import oavi
from repro.core.oavi import OAVIConfig
from repro.core.transform import MinMaxScaler
from repro.data.synthetic import appendix_c
from repro.resilience import Journal, JournalError


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test starts from enabled, unsampled, empty recorder state."""
    obs.configure(enabled=True, sample_every=1)
    obs.reset()
    yield
    obs.configure(enabled=True, sample_every=1)
    obs.reset()


def _span_events():
    return [e for e in obs.trace_events() if e["ph"] == "X"]


# ---------------------------------------------------------------------------
# spans: nesting, thread-safety, sampling


def test_span_nesting_stack():
    assert obs.current_stack() == []
    with obs.span("outer"):
        assert obs.current_stack() == ["outer"]
        with obs.span("inner", d=2):
            assert obs.current_stack() == ["outer", "inner"]
        assert obs.current_stack() == ["outer"]
    assert obs.current_stack() == []
    names = [e["name"] for e in _span_events()]
    # inner exits (and records) before outer
    assert names == ["inner", "outer"]


def test_span_records_duration_and_args():
    with obs.span("work", rows=7) as sp:
        pass
    assert sp.duration_s >= 0.0
    (ev,) = _span_events()
    assert ev["name"] == "work"
    assert ev["args"] == {"rows": 7}
    assert ev["dur"] >= 0.0


def test_spans_are_thread_isolated():
    errors = []
    barrier = threading.Barrier(4)

    def worker(tag):
        try:
            barrier.wait(timeout=10)
            for i in range(50):
                with obs.span(f"{tag}/outer", i=i):
                    with obs.span(f"{tag}/inner"):
                        stack = obs.current_stack()
                        if stack != [f"{tag}/outer", f"{tag}/inner"]:
                            errors.append((tag, stack))
                if obs.current_stack():
                    errors.append((tag, "leak"))
        except Exception as exc:  # pragma: no cover - surfaced via errors
            errors.append((tag, repr(exc)))

    threads = [threading.Thread(target=worker, args=(f"t{k}",)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    # every span from every thread was recorded, each on its own tid
    events = _span_events()
    assert len(events) == 4 * 50 * 2
    by_tag = {}
    for e in events:
        by_tag.setdefault(e["name"].split("/")[0], set()).add(e["tid"])
    assert all(len(tids) == 1 for tids in by_tag.values())
    assert len(set().union(*by_tag.values())) == 4


def test_sampling_keeps_every_nth():
    obs.configure(sample_every=5)
    for _ in range(20):
        with obs.span("sampled"):
            pass
    assert len(_span_events()) == 4


def test_events_are_instant_records():
    obs.event("fit/recompile", signature="(8, 3)")
    (ev,) = obs.trace_events()
    assert ev["ph"] == "i"
    assert ev["args"] == {"signature": "(8, 3)"}


# ---------------------------------------------------------------------------
# disabled: true no-ops, numerics unchanged


def test_disabled_span_is_noop_singleton():
    obs.disable()
    try:
        a = obs.span("x")
        b = obs.span("y", rows=3)
        assert a is b  # shared singleton: zero per-span allocation
        with a:
            assert obs.current_stack() == []
        obs.event("ignored")
        assert obs.trace_events() == []
    finally:
        obs.enable()


def test_disabled_context_restores_state():
    assert obs.enabled()
    with obs.disabled():
        assert not obs.enabled()
        with obs.disabled():
            assert not obs.enabled()
        assert not obs.enabled()
    assert obs.enabled()


def test_metrics_stay_live_when_disabled():
    c = obs.Counter()
    h = obs.Histogram()
    with obs.disabled():
        c.inc(3)
        h.observe(2.0)
    assert c.value == 3
    assert h.count == 1


def test_fit_bit_identical_with_obs_on_and_off():
    X, _ = appendix_c(m=400, seed=0)
    X = MinMaxScaler(dtype="float32").fit_transform(X)
    cfg = OAVIConfig(psi=0.01, engine="fast")
    model_on = oavi.fit(X, cfg)
    with obs.disabled():
        model_off = oavi.fit(X, cfg)
    assert model_on.book.terms == model_off.book.terms
    assert [g.term for g in model_on.generators] == [
        g.term for g in model_off.generators
    ]
    for ga, gb in zip(model_on.generators, model_off.generators):
        assert np.array_equal(ga.coeffs, gb.coeffs)
        assert ga.mse == gb.mse


# ---------------------------------------------------------------------------
# histogram sketch: fidelity, merge algebra, summaries


def _rel_err(approx, exact):
    return abs(approx - exact) / exact


@pytest.mark.parametrize(
    "sampler",
    [
        lambda rng: rng.lognormal(mean=0.0, sigma=1.5, size=50_000),
        lambda rng: rng.pareto(a=1.5, size=50_000) + 1.0,
    ],
    ids=["lognormal", "pareto"],
)
def test_sketch_quantiles_within_one_bucket(sampler):
    vals = sampler(np.random.default_rng(0))
    h = obs.Histogram()
    h.observe_many(vals)
    budget = obs.bucket_relative_error()
    for q in (50.0, 90.0, 99.0, 99.9):
        exact = float(np.percentile(vals, q))
        assert _rel_err(h.quantile(q / 100.0), exact) <= budget


def test_histogram_exact_moments():
    vals = [0.5, 1.0, 2.0, 4.0, 8.0]
    h = obs.Histogram()
    h.observe_many(vals)
    assert h.count == 5
    assert h.sum == pytest.approx(sum(vals))
    assert h.min == 0.5
    assert h.max == 8.0
    assert h.mean == pytest.approx(np.mean(vals))


def test_histogram_underflow_bucket():
    h = obs.Histogram()
    h.observe_many([-1.0, 0.0, 1.0])
    assert h.count == 3
    assert h.quantile(0.0) == 0.0  # non-positive values report as 0.0
    assert h.quantile(1.0) >= 1.0


def test_histogram_merge_is_associative_and_exact():
    rng = np.random.default_rng(7)
    parts = [rng.lognormal(0.0, 1.0, 5000) for _ in range(3)]

    def sketch(chunks):
        h = obs.Histogram()
        for c in chunks:
            h.observe_many(c)
        return h

    a, b, c = (sketch([p]) for p in parts)
    left = sketch([parts[0]]).merge(sketch([parts[1]])).merge(sketch([parts[2]]))
    right = sketch([parts[0]]).merge(sketch([parts[1]]).merge(sketch([parts[2]])))
    whole = sketch(parts)
    for q in (0.5, 0.9, 0.99):
        assert left.quantile(q) == right.quantile(q) == whole.quantile(q)
    assert left.count == right.count == whole.count == 15000
    assert left.sum == pytest.approx(whole.sum)
    assert left.min == whole.min and left.max == whole.max
    # merge() did not mutate its argument's identity semantics
    assert a.count == b.count == c.count == 5000


def test_histogram_summary_keys():
    h = obs.Histogram()
    h.observe_many([1.0, 2.0, 3.0])
    s = h.summary()
    assert set(s) == {"count", "sum", "mean", "min", "max", "p50", "p90", "p99", "p999"}
    empty = obs.Histogram().summary()
    assert empty["count"] == 0


def test_percentile_summary_helper():
    s = obs.percentile_summary([1.0, 2.0, 4.0], unit_scale=1e3)
    assert s["count"] == 3
    assert s["max"] == pytest.approx(4000.0, rel=obs.bucket_relative_error())
    assert obs.percentile_summary([]) is None


def test_registry_labels_and_snapshot():
    reg = obs.Registry()
    reg.counter("fit.recompiles", backend="local").inc()
    reg.counter("fit.recompiles", backend="shard").inc(2)
    reg.histogram("fit.seconds", backend="local").observe(0.5)
    snap = reg.snapshot()
    by_key = {(r["name"], tuple(sorted(r.get("labels", {}).items()))): r for r in snap}
    assert by_key[("fit.recompiles", (("backend", "local"),))]["value"] == 1
    assert by_key[("fit.recompiles", (("backend", "shard"),))]["value"] == 2
    assert by_key[("fit.seconds", (("backend", "local"),))]["count"] == 1


# ---------------------------------------------------------------------------
# trace export: Chrome-trace schema


def test_export_trace_validates_against_schema(tmp_path):
    with obs.span("fit", m=100):
        with obs.span("fit/degree", d=2):
            pass
    obs.event("fit/compile", signature="sig")
    path = obs.export_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    payload = obs.validate_chrome_trace(doc)
    assert doc["displayTimeUnit"] == "ms"
    names = {e["name"] for e in payload}
    assert names == {"fit", "fit/degree", "fit/compile"}
    # metadata rows announce the process and each thread
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    assert any(e["name"] == "thread_name" for e in meta)
    pid = os.getpid()
    assert all(e["pid"] == pid for e in payload)


@pytest.mark.parametrize(
    "doc",
    [
        [],  # not a dict
        {"events": []},  # wrong container key
        {"traceEvents": [{"ph": "X"}]},  # missing required keys
        {"traceEvents": [{"name": "x", "ph": "Z", "ts": 0, "pid": 1, "tid": 1}]},
        {"traceEvents": [{"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 1}]},
    ],
    ids=["not-dict", "wrong-key", "missing-keys", "bad-phase", "x-without-dur"],
)
def test_validate_chrome_trace_rejects_malformed(doc):
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(doc)


def test_trace_buffer_bounded_with_drop_count():
    obs.configure(trace_capacity=16)
    try:
        for i in range(40):
            obs.event("tick", i=i)
        snap = obs.snapshot()
        assert snap["trace"]["events"] == 16
        assert snap["trace"]["dropped"] == 24
        # survivors are the newest events
        kept = [e["args"]["i"] for e in obs.trace_events()]
        assert kept == list(range(24, 40))
    finally:
        obs.configure(trace_capacity=100_000)


def test_metrics_export_jsonl_roundtrip(tmp_path):
    obs.registry().counter("journal.appends", kind="activated").inc(2)
    obs.registry().histogram("fit.seconds", backend="local").observe(1.5)
    path = obs.export_metrics(str(tmp_path / "metrics.jsonl"))
    rows = [json.loads(line) for line in open(path)]
    names = {r["name"] for r in rows}
    assert {"journal.appends", "fit.seconds"} <= names


# ---------------------------------------------------------------------------
# fit timing contract (satellite: time_total vs degree_times reconciliation)


def _assert_timing_contract(stats):
    total = stats["time_total"]
    parts = (
        stats["time_setup"]
        + stats["time_degrees"]
        + stats["time_finalize"]
        + stats["time_unattributed"]
    )
    # exact by construction (one subtraction defines the residual)
    assert total == pytest.approx(parts, abs=1e-9)
    assert stats["time_setup"] >= 0.0
    assert stats["time_degrees"] >= 0.0
    assert stats["time_finalize"] >= 0.0
    # the public per-degree list matches the unrounded accumulator up to its
    # 6-decimal rounding
    assert sum(stats["degree_times"]) == pytest.approx(
        stats["time_degrees"], abs=1e-6 * max(1, len(stats["degree_times"]))
    )


def test_fit_stats_timing_contract_local():
    X, _ = appendix_c(m=400, seed=1)
    X = MinMaxScaler(dtype="float32").fit_transform(X)
    model = oavi.fit(X, OAVIConfig(psi=0.01, engine="fast"))
    _assert_timing_contract(model.stats)


def test_fit_stats_timing_contract_streaming():
    from repro import streaming

    X, _ = appendix_c(m=600, seed=2)
    X = MinMaxScaler(dtype="float32").fit_transform(X)
    model = streaming.fit(
        streaming.ArraySource(X), OAVIConfig(psi=0.01, engine="fast"), chunk_rows=256
    )
    _assert_timing_contract(model.stats)


# ---------------------------------------------------------------------------
# journal compaction (satellite: Journal.compact)


def _fill_journal(j):
    j.append("base_fitted", version=0)
    j.append("increment", update=1)
    j.append("refit", update=1)
    j.append("activated", version=1, update=1)
    j.append("increment", update=2)
    j.append("refit", update=2)
    j.append("activated", version=2, update=2)
    j.append("increment", update=3)


def test_journal_compact_keeps_resume_state(tmp_path):
    path = str(tmp_path / "run.journal")
    with Journal(path) as j:
        _fill_journal(j)
        dropped = j.compact()
        assert dropped == 5
        kinds = [r["kind"] for r in j.replay()]
        # last activation and everything after it survive, plus the newest
        # base_fitted record the resume gate reads
        assert kinds == ["base_fitted", "activated", "increment"]
        assert j.last("activated")["version"] == 2
        assert j.last("base_fitted")["version"] == 0
        # appends continue with monotonically increasing seq
        rec = j.append("refit", update=3)
        assert rec["seq"] > j.last("activated")["seq"]

    # a fresh reader sees the compacted file as a valid journal
    with Journal(path) as j2:
        assert [r["kind"] for r in j2.replay()] == [
            "base_fitted",
            "activated",
            "increment",
            "refit",
        ]


def test_journal_compact_noop_cases(tmp_path):
    with Journal(str(tmp_path / "empty.journal")) as j:
        assert j.compact() == 0
    with Journal(str(tmp_path / "no-anchor.journal")) as j:
        j.append("base_fitted", version=0)
        j.append("increment", update=1)
        assert j.compact() == 0  # nothing to cut before: no anchor record
        assert len(j.replay()) == 2


def test_journal_compact_idempotent(tmp_path):
    with Journal(str(tmp_path / "twice.journal")) as j:
        _fill_journal(j)
        assert j.compact() > 0
        assert j.compact() == 0
        assert [r["kind"] for r in j.replay()] == [
            "base_fitted",
            "activated",
            "increment",
        ]


def test_journal_compact_preserves_crc_integrity(tmp_path):
    path = str(tmp_path / "crc.journal")
    with Journal(path) as j:
        _fill_journal(j)
        j.compact()
    # every surviving line still carries a valid CRC
    with Journal(path) as j2:
        for rec in j2.replay():
            assert rec["crc"]


def test_journal_compact_counts_metric(tmp_path):
    before = obs.registry().counter("journal.appends", kind="activated").value
    with Journal(str(tmp_path / "m.journal")) as j:
        _fill_journal(j)
    after = obs.registry().counter("journal.appends", kind="activated").value
    assert after - before == 2
