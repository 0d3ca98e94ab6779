"""The program's spans through Algorithm 2's fit.

Each layer boundary of ``VanishingIdealClassifier.fit`` opens a span of a
fixed name, nested as the layers are; the spans that sit beside a ``stats``
time wrap the same statements; and every span also reaches an open
``jax.profiler`` session on its host plane, where the benchmark reads it.
"""

import glob
import os

import numpy as np
import pytest

import jax

from repro import obs
from repro.core import oavi
from repro.core.oavi import OAVIConfig
from repro.core.pipeline import PipelineConfig, VanishingIdealClassifier
from repro.core.svm import LinearSVMConfig
from repro.core.transform import MinMaxScaler
from repro.data.synthetic import appendix_c

# span -> the span it sits in ("fit" is the fit driver's FitScope span)
PARENT = {
    "pipeline/scale": "pipeline/fit",
    "pipeline/generators": "pipeline/fit",
    "pipeline/transform": "pipeline/fit",
    "pipeline/svm": "pipeline/fit",
    "fit": "pipeline/generators",
    "fit/prepare": "fit",
    "fit/border": "fit",
    "fit/degree": "fit",
    "fit/collect": "fit",
    "transform/plan": "pipeline/transform",
    "transform/eval": "pipeline/transform",
    "svm/prepare": "pipeline/svm",
    "svm/loop": "pipeline/svm",
}
NAMES = sorted(set(PARENT) | {"pipeline/fit"})


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.configure(enabled=True, sample_every=1)
    obs.reset()
    yield
    obs.configure(enabled=True, sample_every=1)
    obs.reset()


def _data(m=600):
    X, y = appendix_c(m=m, seed=3)
    return X, y


def _classifier():
    return VanishingIdealClassifier(PipelineConfig(
        method="fast", psi=0.01, svm=LinearSVMConfig(max_iter=300)))


def _spans():
    return [e for e in obs.trace_events() if e["ph"] == "X"]


def _inside(child, parent, slack_us=1.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us)


def test_classifier_fit_records_the_layer_spans_nested():
    clf = _classifier().fit(*_data())
    spans = _spans()
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert set(NAMES) <= set(by_name)
    assert len(by_name["pipeline/fit"]) == 1
    degrees = len(clf.models[0].stats["degrees"])
    assert len(by_name["fit/degree"]) == len(by_name["fit/collect"]) == degrees
    assert len(by_name["fit/border"]) >= degrees  # the last finds no border
    for child, parent in PARENT.items():
        for e in by_name[child]:
            assert any(_inside(e, p) for p in by_name[parent]), (child, parent)


def test_local_fit_loop_records_prepare_border_and_collect():
    X, _ = _data(400)
    model = oavi.fit(MinMaxScaler(dtype="float32").fit_transform(X),
                     OAVIConfig(psi=0.01, engine="fast"))
    names = [e["name"] for e in _spans()]
    degrees = len(model.stats["degrees"])
    assert names.count("fit/prepare") == 1
    assert names.count("fit/collect") == names.count("fit/degree") == degrees
    assert names.count("fit/border") == degrees + 1  # the last finds no border
    (fit,) = [e for e in _spans() if e["name"] == "fit"]
    assert all(_inside(e, fit) for e in _spans() if e["name"].startswith("fit/"))


@pytest.mark.parametrize("span,key", [("pipeline/svm", "time_svm"),
                                      ("pipeline/transform", "time_transform"),
                                      ("pipeline/fit", "time_total")])
def test_pipeline_spans_match_the_stats_times(span, key):
    clf = _classifier().fit(*_data())
    (e,) = [e for e in _spans() if e["name"] == span]
    assert abs(e["dur"] * 1e-6 - clf.stats[key]) < 1e-3


def test_disabled_obs_records_no_spans():
    with obs.disabled():
        _classifier().fit(*_data())
    assert _spans() == []


def _host_span_names(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            names += [ev.name for line in plane.lines for ev in line.events
                      if ev.name in NAMES]
    return names


def test_spans_reach_the_profilers_host_plane(tmp_path):
    X, y = _data()
    _classifier().fit(X, y)  # compile outside the traced fit
    obs.reset()
    with jax.profiler.trace(str(tmp_path / "on")):
        clf = _classifier().fit(X, y)
    names = _host_span_names(str(tmp_path / "on"))
    assert sorted(set(names)) == NAMES
    recorded = [e["name"] for e in _spans()]
    assert sorted(names) == sorted(n for n in recorded if n in NAMES)
    with obs.disabled(), jax.profiler.trace(str(tmp_path / "off")):
        clf_off = _classifier().fit(X, y)
    assert _host_span_names(str(tmp_path / "off")) == []
    np.testing.assert_array_equal(clf.svm.W, clf_off.svm.W)
