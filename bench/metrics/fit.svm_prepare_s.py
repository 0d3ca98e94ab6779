"""Seconds per fit in the SVM's set-up (the program's ``svm/prepare`` span:
features, labels and bias column to the device, power iteration to the step
size), read from the trace's host plane inside the window.  The span is host
time: device work it dispatches may finish inside ``svm/loop``."""

from bench.spans import per_fit


def read(run):
    return per_fit(run, "svm/prepare")
