"""Seconds per fit in the pipeline's scaler (the program's ``pipeline/scale``
span: ``MinMaxScaler.fit_transform`` of the training rows, on the host), read
from the trace's host plane inside the window."""

from bench.spans import per_fit


def read(run):
    return per_fit(run, "pipeline/scale")
