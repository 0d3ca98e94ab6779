"""The program's spans in a traced run, read from the trace's host plane.

The program enters a ``jax.profiler.TraceAnnotation`` for each of its spans,
so the profiler writes them on its host plane, on the clock of the device's
operations.  This module reads the spans whose names start with
``PREFIXES`` inside the benchmark's ``bench/window`` annotation (names are
matched by their start, so host events such as ``SubbytePacker pipeline``
never count), gives their seconds per fit (over the ``pipeline/fit``
spans), and puts each idle interval of the first device down to the
innermost program span that covers it.

``for_run`` finds the run's trace, reads it once per run and prints the
idle-by-span table to standard error.  It returns None where nothing can
be read: no trace, or a program that writes no ``pipeline/fit`` span.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace as trace_mod

PREFIXES = ("pipeline/", "fit/", "svm/", "transform/")
FIT = "pipeline/fit"
OUTSIDE = "(no program span)"


@dataclasses.dataclass
class Spans:
    window: Tuple[float, float]  # bench/window, seconds on the trace's clock
    events: List[Tuple[float, float, str]]  # (start, end, name) inside the window

    def count(self, name: str) -> int:
        return sum(1 for e in self.events if e[2] == name)

    def per_fit(self, name: str) -> Optional[float]:
        """Seconds in spans named ``name`` over the number of fits; None
        where the window holds no fit or no such span."""
        fits = self.count(FIT)
        hits = [e - s for s, e, n in self.events if n == name]
        if not fits or not hits:
            return None
        return float(sum(hits)) / fits


def read_spans(pd) -> Optional[Spans]:
    """The program's spans of a ``jax.profiler.ProfileData`` inside its
    ``bench/window``; None without the window."""
    window = None
    events = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == trace_mod.WINDOW:
                    window = (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                elif name.startswith(PREFIXES):
                    s = ev.start_ns * 1e-9
                    events.append((s, s + ev.duration_ns * 1e-9, name))
    if window is None:
        return None
    w0, w1 = window
    inside = sorted(e for e in events if e[0] >= w0 and e[1] <= w1)
    return Spans(window=window, events=inside)


def busy_before(busy: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Seconds of the merged, ordered ``busy`` intervals that lie before
    each instant of ``t``."""
    if not len(busy):
        return np.zeros_like(t)
    done = np.concatenate([[0.0], np.cumsum(busy[:, 1] - busy[:, 0])])
    k = np.searchsorted(busy[:, 0], t, side="right")  # intervals begun by t
    prev = np.maximum(k - 1, 0)
    part = np.clip(t - busy[prev, 0], 0.0, busy[prev, 1] - busy[prev, 0])
    return np.where(k > 0, done[prev] + part, 0.0)


def idle_by_span(ops, spans: Spans) -> List[Tuple[str, float]]:
    """Idle seconds of the window by the innermost program span covering
    them (the latest-starting span that holds the instant), longest first;
    idle time under no program span is listed as ``OUTSIDE``.  ``ops`` are
    the first device's operations (``bench.trace.Op``)."""
    w0, w1 = spans.window
    iv = np.asarray([(max(o.start, w0), min(o.start + o.dur, w1)) for o in ops
                     if o.start + o.dur > w0 and o.start < w1]).reshape(-1, 2)
    _, busy = trace_mod.union_seconds(iv)
    starts = np.asarray([s for s, _, _ in spans.events])
    ends = np.asarray([e for _, e, _ in spans.events])
    # between two consecutive span edges the innermost span does not change
    edges = np.unique(np.clip(np.concatenate([[w0, w1], starts, ends]), w0, w1))
    a, b = edges[:-1], edges[1:]
    idle = (b - a) - (busy_before(busy, b) - busy_before(busy, a))
    out: Dict[str, float] = {}
    for mid, sec in zip(0.5 * (a + b), idle):
        if sec <= 0:
            continue
        cover = np.nonzero((starts <= mid) & (ends >= mid))[0]
        name = spans.events[cover[np.argmax(starts[cover])]][2] if len(cover) else OUTSIDE
        out[name] = out.get(name, 0.0) + float(sec)
    return sorted(out.items(), key=lambda kv: -kv[1])


def format_idle(table: List[Tuple[str, float]]) -> str:
    total = sum(sec for _, sec in table) or 1.0
    return "; ".join(f"{name} {sec:.4f} s {100.0 * sec / total:.1f} %" for name, sec in table)


def run_spans(summary) -> Optional[Spans]:
    """The program's spans in the trace whose reduction is ``summary``.  The
    harness writes each traced run's trace under a fresh ``bench_*``
    directory of the temporary directory; newest first, the first trace
    there whose window is as long as the summary's is this run's."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(tempfile.gettempdir(), "bench_*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        spans = read_spans(ProfileData.from_file(path))
        if spans is not None and abs(spans.window[1] - spans.window[0]
                                     - summary.window_s) < 1e-6:
            return spans
    return None


_LAST: Tuple[object, Optional[Spans]] = (None, None)  # (summary, its spans)


def for_run(run) -> Optional[Spans]:
    """The program's spans of a traced run, read once per run; prints the
    idle-by-span table to standard error the first time."""
    global _LAST
    summary = run.trace
    if summary is None:
        return None
    if _LAST[0] is summary:
        return _LAST[1]
    t0 = time.perf_counter()
    spans = run_spans(summary)
    if spans is not None:
        fits, fit_s = spans.count(FIT), spans.per_fit(FIT)
        print(f"bench: program spans read in {time.perf_counter() - t0:.1f} s: {fits} fits"
              + (f" of {fit_s:.4f} s, {len(spans.events) / fits:.1f} spans a fit"
                 if fit_s else ""), file=sys.stderr)
        print(f"bench: idle by span: {format_idle(idle_by_span(summary.ops, spans))}",
              file=sys.stderr)
        if not fit_s:
            spans = None  # a program that writes no fit spans
    _LAST = (summary, spans)
    return spans


def per_fit(run, name: str) -> Optional[float]:
    spans = for_run(run)
    return spans.per_fit(name) if spans is not None else None
