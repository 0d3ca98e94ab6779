"""Seconds per fit in the fit driver's set-up before its first degree (the
program's ``fit/prepare`` span: Pearson ordering, the row stack and mask,
their copy to the device, the initial evaluation matrix and IHB state), read
from the trace's host plane inside the window."""

from bench.spans import per_fit


def read(run):
    return per_fit(run, "fit/prepare")
