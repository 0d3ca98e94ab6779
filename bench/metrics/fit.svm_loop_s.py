"""Seconds per fit in the SVM's FISTA loop (the program's ``svm/loop`` span:
the jitted loop's dispatch until its weights, bias and iteration count are on
the host), read from the trace's host plane inside the window."""

from bench.spans import per_fit


def read(run):
    return per_fit(run, "svm/loop")
