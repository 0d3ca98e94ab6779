"""The peaks table: keyed by device kind, with its source; an unknown
device is an error, never a default."""

import json

import pytest

from bench import deploy, harness


def test_peaks_have_a_source_and_the_v5e():
    table = json.loads((deploy.BENCH / "peaks.json").read_text())
    assert "cloud.google.com" in table["source"]
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")
