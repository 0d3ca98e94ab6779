"""The trace reduction: busy time as a union of intervals, idle gaps
labelled by the host, and kernel time by name, on a small trace in the
layout of a TPU v5e trace (``data/tpu_layout.txtpb``)."""

import os

import numpy as np
import pytest

from bench import trace

LAYOUT = os.path.join(os.path.dirname(__file__), "data", "tpu_layout.txtpb")


def test_union_of_overlapping_intervals():
    iv = np.array([[0.0, 1.0], [0.5, 2.0], [3.0, 4.0], [3.5, 3.6]])
    total, merged = trace.union_seconds(iv)
    assert total == pytest.approx(3.0)
    assert merged.tolist() == [[0.0, 2.0], [3.0, 4.0]]


def test_idle_gaps_are_labelled_by_the_most_specific_host_event():
    merged = np.array([[1.0, 2.0], [5.0, 6.0]])
    host = [(0.0, 10.0, "outer"), (2.1, 4.9, "inner"), (6.0, 6.5, "tail")]
    gaps = trace.idle_gaps(merged, 0.0, 8.0, host)
    assert gaps[0] == ("inner", pytest.approx(3.0))  # 2..5, the longest
    assert [g[1] for g in gaps] == pytest.approx([3.0, 2.0, 1.0])
    assert gaps[1][0] == "outer"  # 6..8: 'tail' covers only a quarter


def test_idle_share():
    s = trace.Summary(busy_s=0.25, window_s=1.0, ops=[], gaps=[], op_totals={})
    assert trace.idle_pct(s) == pytest.approx(75.0)
    assert trace.idle_pct(None) is None


def test_trace_in_the_tpu_layout():
    from jax.profiler import ProfileData

    with open(LAYOUT) as f:
        pd = ProfileData.from_text_proto(f.read())
    s = trace.reduce_profile(pd, 1)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.005)  # [0, 3] ms and [5, 7] ms
    assert trace.idle_pct(s) == pytest.approx(50.0)
    assert s.kernel_seconds("gram_update_acc") == pytest.approx(0.002)
    assert s.kernel_seconds("ihb_update") == pytest.approx(0.002)
    assert s.kernel_seconds("flash_attention") is None
    assert s.gaps == [("device_get", pytest.approx(0.003)),
                      ("PjitFunction(degree_step)", pytest.approx(0.002))]
    ops = dict(s.breakdown()["device_ops"])
    assert ops == {"custom-call.1": pytest.approx(0.002), "fusion.2": pytest.approx(0.002),
                   "custom-call.3": pytest.approx(0.002)}
