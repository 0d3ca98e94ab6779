"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
benchmark prints: device busy time (the union of the intervals in which an
operation ran, averaged over the chips), the traced window, device time by
operation and by kernel name, and the longest idle gaps labelled by what the
host was doing in them.

The window is the benchmark's own ``bench/window`` annotation; device
operations are the events of each ``/device:TPU:<i>`` plane's ``XLA Ops``
line.  A kernel is matched by name: a custom call matches ``kernel`` when the
name appears in the event's name or in one of its string statistics (the
HLO op name, its long name, the program it belongs to).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench/window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Op:
    name: str
    label: str  # what the op is known as: the op name plus its strings
    start: float  # seconds, on the trace's clock
    dur: float


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    ops: List[Op]  # device ops of the first device inside the window
    gaps: List[Tuple[str, float]]  # longest idle gaps, labelled by the host
    op_totals: Dict[str, float]

    def kernel_seconds(self, kernel: str) -> Optional[float]:
        """Device seconds of the ops that carry ``kernel`` in their name or
        strings; None when no op does (the trace does not name it)."""
        hits = [o.dur for o in self.ops if kernel in o.label]
        return float(sum(hits)) if hits else None

    def breakdown(self) -> Dict:
        top = sorted(self.op_totals.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


class Tracer:
    """A ``jax.profiler`` window written under ``<tmpdir>/trace``, with the
    benchmark's window annotation open while it records."""

    def __init__(self, tmpdir: str):
        self.dir = os.path.join(tmpdir, "trace")
        self._annotation = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self._annotation = jax.profiler.TraceAnnotation(WINDOW)
        self._annotation.__enter__()

    def stop(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, num_devices: int) -> Summary:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return reduce_file(max(paths, key=os.path.getmtime), num_devices)


def _strings(event) -> str:
    """The event's name, and for a custom call (where Pallas kernels live)
    its string statistics too; other ops are not read further, which keeps
    the reduction of a long trace short."""
    if "custom" not in event.name:
        return event.name
    return " ".join([event.name] + [v for _, v in event.stats if isinstance(v, str)])


def union_seconds(intervals: np.ndarray) -> Tuple[float, np.ndarray]:
    """Total length of the union of ``(start, end)`` rows, and the merged
    intervals in order."""
    if len(intervals) == 0:
        return 0.0, np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    m = np.asarray(merged)
    return float(np.sum(m[:, 1] - m[:, 0])), m


def reduce_file(path: str, num_devices: Optional[int] = None) -> Summary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), num_devices)


def ops_line(plane):
    """A device plane's line of operations: ``XLA Ops``, or, in a trace that
    names its lines otherwise, the line with the most events."""
    lines = list(plane.lines)
    for line in lines:
        if line.name == OPS_LINE:
            return line
    return max(lines, key=lambda ln: sum(1 for _ in ln.events), default=None)


def reduce_profile(pd, num_devices: Optional[int] = None) -> Summary:
    """The summary of a ``jax.profiler.ProfileData``."""
    window = None
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            line = ops_line(plane)
            if line is not None:
                devices.append((plane.name, line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                    if ev.name == WINDOW:
                        window = (s, e)
                    elif ev.duration_ns > 0:
                        host.append((s, e, ev.name))
    devices.sort(key=lambda pl: int(pl[0][len(DEVICE_PREFIX):] or 0))
    if num_devices is not None:
        devices = devices[:num_devices]

    per_device_ops = []
    for _, line in devices:
        ops = []
        for ev in line.events:
            s = ev.start_ns * 1e-9
            ops.append(Op(ev.name, _strings(ev), s, ev.duration_ns * 1e-9))
        per_device_ops.append(ops)
    if window is None:
        starts = [o.start for ops in per_device_ops for o in ops]
        ends = [o.start + o.dur for ops in per_device_ops for o in ops]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    w0, w1 = window

    busy = []
    merged0 = np.zeros((0, 2))
    for i, ops in enumerate(per_device_ops):
        iv = np.asarray([(max(o.start, w0), min(o.start + o.dur, w1)) for o in ops
                         if o.start + o.dur > w0 and o.start < w1]).reshape(-1, 2)
        total, merged = union_seconds(iv)
        busy.append(total)
        if i == 0:
            merged0 = merged
    first = [o for o in (per_device_ops[0] if per_device_ops else [])
             if o.start + o.dur > w0 and o.start < w1]
    totals: Dict[str, float] = {}
    for o in first:
        totals[o.name] = totals.get(o.name, 0.0) + o.dur
    return Summary(
        busy_s=float(np.mean(busy)) if busy else 0.0,
        window_s=float(w1 - w0),
        ops=first,
        gaps=idle_gaps(merged0, w0, w1, host),
        op_totals=totals,
    )


def idle_gaps(merged: np.ndarray, w0: float, w1: float,
              host: List[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """The longest stretches of the window with no device op, each labelled
    by the most specific host event that covers most of it."""
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:TOP]
    if not host:
        return [("(no host events)", float(e - s)) for s, e in gaps]
    hs = np.asarray([h[0] for h in host])
    he = np.asarray([h[1] for h in host])
    out = []
    for s, e in gaps:
        overlap = np.minimum(he, e) - np.maximum(hs, s)
        cand = np.nonzero(overlap >= 0.5 * (e - s))[0]
        if len(cand) == 0:
            cand = np.nonzero(overlap > 0)[0]
        if len(cand) == 0:
            out.append(("(no host event)", float(e - s)))
            continue
        best = cand[np.argmin(he[cand] - hs[cand])]
        out.append((host[best][2], float(e - s)))
    return out


def idle_pct(summary: Optional[Summary]) -> Optional[float]:
    """Share of the window with no device op, in %; None without a trace."""
    if summary is None or summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
