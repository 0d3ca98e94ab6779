"""The program's spans read from the trace's host plane: seconds per fit
and idle time by span, on a small hand-written trace in the layout of a TPU
v5e trace (``data/tpu_spans.txtpb``), and the span metrics of a small
traced run on the CPU."""

import os
import time

import pytest

from bench import harness, spans, trace
from bench.tests import small

LAYOUT = os.path.join(os.path.dirname(__file__), "data", "tpu_spans.txtpb")
MS = 1e-3


def layout():
    from jax.profiler import ProfileData

    with open(LAYOUT) as f:
        return ProfileData.from_text_proto(f.read())


def test_span_seconds_per_fit_in_the_tpu_layout():
    s = spans.read_spans(layout())
    assert s.window == pytest.approx((0.0, 20 * MS))
    assert s.count(spans.FIT) == 2
    # the scaler span after the window and the host's "SubbytePacker
    # pipeline" event are not counted
    assert s.count("pipeline/scale") == 2
    assert {n for _, _, n in s.events} == {
        "pipeline/fit", "pipeline/scale", "fit/prepare", "svm/prepare", "svm/loop"}
    assert s.per_fit("pipeline/scale") == pytest.approx(1 * MS)
    assert s.per_fit("fit/prepare") == pytest.approx(1 * MS)
    assert s.per_fit("svm/prepare") == pytest.approx(1 * MS)
    assert s.per_fit("svm/loop") == pytest.approx(3 * MS)
    assert s.per_fit("transform/eval") is None


def test_idle_by_innermost_span_in_the_tpu_layout():
    pd = layout()
    summary = trace.reduce_profile(pd, 1)
    table = dict(spans.idle_by_span(summary.ops, spans.read_spans(pd)))
    assert table == pytest.approx({
        spans.OUTSIDE: 3 * MS,  # before, between and after the fits
        "pipeline/scale": 2 * MS,
        "fit/prepare": 2 * MS,
        "svm/prepare": 2 * MS,
        "svm/loop": 0.5 * MS,
        "pipeline/fit": 0.5 * MS,  # inside a fit, under none of its parts
    })
    assert sum(table.values()) == pytest.approx(summary.window_s - summary.busy_s)


def test_no_window_or_no_fit_spans_read_as_nothing():
    s = spans.Spans(window=(0.0, 1.0), events=[(0.1, 0.2, "svm/loop")])
    assert s.per_fit("svm/loop") is None
    assert spans.idle_by_span([], s) == [(spans.OUTSIDE, pytest.approx(0.9)),
                                         ("svm/loop", pytest.approx(0.1))]


def test_small_traced_run_reports_the_span_metrics(monkeypatch, capsys):
    # the CPU has no peaks in the table; the kernel rooflines read nothing here
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {})
    result = harness.run_cell("appendix_c-fit", 20260917, small.SECONDS, True,
                              time.perf_counter(), require_chip=False,
                              overrides=small.SMALL["appendix_c-fit"])
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("fit.scale_s", "fit.prepare_s", "fit.svm_prepare_s", "fit.svm_loop_s",
                 "fit.svm_iters", "fit.svm_s", "fit.transform_s", "fit.degree_steps_s",
                 "fit.host_between_degrees_s", "fit.device_idle_pct"):
        assert name in m, name
    assert m["fit.svm_prepare_s"] + m["fit.svm_loop_s"] == pytest.approx(m["fit.svm_s"],
                                                                          rel=0.03)
    assert 1 <= m["fit.svm_iters"] <= 10_000
    assert "bench: idle by span: " in capsys.readouterr().err
