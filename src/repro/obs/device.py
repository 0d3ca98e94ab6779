"""Device-level observability: compile time, memory, profiles.

Three capabilities, all best-effort and all safe without jax installed:

- **XLA compile accounting** — a process-global listener on jax's internal
  event-duration channel accumulates ``backend_compile`` seconds, and
  :class:`CompileWindow` attributes the delta over a code region (a fit, an
  engine warmup).  This measures the *actual* XLA compile, not the Python
  call that happened to trigger it.
- **Live-memory timeline** — :func:`sample_memory` unifies the allocator
  high-water mark (TPU/GPU) and live-array accounting (CPU) into one
  sampling point that updates fit-stats peaks, sets registry gauges, and
  emits a Chrome counter event so traces show memory over time.
- **Profiler windows** — :func:`profile_window` opens a ``jax.profiler``
  trace when ``OBS_JAX_PROFILE=<dir>`` is set, so XLA device timelines
  interleave with obs spans (each span enters a ``TraceAnnotation``).

Gating: everything here is additionally gated by ``OBS_DEVICE`` (default
on) AND :func:`repro.obs.enabled` — ``obs.disabled()`` therefore yields the
same zero-instrumentation path the overhead benchmarks compare against.
None of it ever changes what a fit or transform computes.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from .core import counter_event, enabled, event, registry

__all__ = [
    "CompileWindow",
    "compile_snapshot",
    "device_enabled",
    "device_memory_stats",
    "live_buffer_bytes",
    "profile_window",
    "sample_memory",
]

_BACKEND_COMPILE_SUFFIX = "backend_compile_duration"


def _env_flag(name: str, default: int) -> bool:
    try:
        return int(os.environ.get(name, default)) != 0
    except ValueError:
        return default != 0


def device_enabled() -> bool:
    """Device-level capture is on: ``OBS_DEVICE`` (default 1) and obs enabled."""
    return enabled() and _env_flag("OBS_DEVICE", 1)


# ---------------------------------------------------------------------------
# XLA compile accounting
# ---------------------------------------------------------------------------

_COMPILE_LOCK = threading.Lock()
_COMPILE = {"seconds": 0.0, "count": 0}
_LISTENER = {"state": None}  # None = not yet tried, True = live, False = n/a


def _on_event_duration(name: str, secs: float, **_kw) -> None:
    if not name.endswith(_BACKEND_COMPILE_SUFFIX):
        return
    with _COMPILE_LOCK:
        _COMPILE["seconds"] += secs
        _COMPILE["count"] += 1
    event("device/xla_compile", seconds=round(secs, 6))


def _ensure_listener() -> bool:
    if _LISTENER["state"] is None:
        try:  # jax._src.monitoring is semi-private; degrade to "unavailable"
            from jax._src import monitoring

            monitoring.register_event_duration_secs_listener(_on_event_duration)
            _LISTENER["state"] = True
        except Exception:
            _LISTENER["state"] = False
    return bool(_LISTENER["state"])


def compile_snapshot() -> Tuple[float, int]:
    """Cumulative (seconds, count) of XLA backend compiles this process."""
    _ensure_listener()
    with _COMPILE_LOCK:
        return _COMPILE["seconds"], _COMPILE["count"]


class CompileWindow:
    """Delta of XLA backend-compile time over a ``with`` region.

    The listener is process-global, so compiles triggered concurrently by
    *other* threads land in every open window — single-fit attribution is
    exact in the (usual) single-threaded fit case and an upper bound
    otherwise.  ``seconds``/``count`` are 0 until exit, and stay 0 when the
    monitoring channel is unavailable.
    """

    __slots__ = ("seconds", "count", "_s0", "_c0")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def __enter__(self) -> "CompileWindow":
        self._s0, self._c0 = compile_snapshot()
        return self

    def __exit__(self, *exc) -> None:
        s1, c1 = compile_snapshot()
        self.seconds = s1 - self._s0
        self.count = c1 - self._c0


# ---------------------------------------------------------------------------
# Live-memory timeline
# ---------------------------------------------------------------------------


def device_memory_stats() -> Dict:
    """Best-effort ``memory_stats()`` of the first local device.  TPU/GPU
    runtimes report allocator counters (``peak_bytes_in_use``); CPU returns
    nothing — callers must treat every key as optional."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return {}
    return dict(stats or {})


def live_buffer_bytes() -> Optional[int]:
    """Total bytes of all live device arrays — the measured fallback for the
    memory benchmarks on backends without allocator stats (this container's
    CPU).  Dominated by the persistent fit buffers (A, IHB state), which is
    exactly the footprint the streaming fit is built to flatten."""
    try:
        import jax

        return int(sum(x.nbytes for x in jax.live_arrays()))
    except Exception:
        return None


def sample_memory(stats: Optional[Dict] = None) -> Dict:
    """One memory-timeline sample: gauges, a trace counter, and stats peaks.

    Updates ``stats["peak_bytes"]`` (allocator high-water, where available)
    and ``stats["live_bytes_peak"]`` (live-array accounting) in place when a
    stats dict is given — the unified replacement for the ad-hoc
    ``peak_bytes`` plumbing the fit loops used to carry.  Always refreshes
    the ``device.live_bytes`` / ``device.peak_bytes`` registry gauges and,
    when obs recording is on, appends a ``device/memory`` counter event so
    exported traces show the memory timeline.  Returns the raw sample.
    """
    out: Dict = {}
    live = live_buffer_bytes()
    if live is not None:
        out["live_bytes"] = live
        if stats is not None:
            stats["live_bytes_peak"] = max(live, int(stats.get("live_bytes_peak") or 0))
    peak = device_memory_stats().get("peak_bytes_in_use")
    if peak is not None:
        out["peak_bytes"] = int(peak)
        if stats is not None:
            stats["peak_bytes"] = max(int(peak), int(stats.get("peak_bytes") or 0))
    if not out:
        return out
    reg = registry()
    if live is not None:
        reg.gauge("device.live_bytes").set(float(live))
        reg.gauge("device.live_bytes_peak").set_max(float(live))
    if peak is not None:
        reg.gauge("device.peak_bytes").set(float(peak))
    if device_enabled():
        # counter args must stay numeric: Perfetto stacks them as series
        counter_event("device/memory", **{k: float(v) for k, v in out.items()})
    return out


# ---------------------------------------------------------------------------
# jax.profiler trace windows
# ---------------------------------------------------------------------------

_PROFILE_LOCK = threading.Lock()
_PROFILE_ACTIVE = {"on": False}


class _ProfileWindow:
    """One ``jax.profiler`` capture window; inner/overlapping windows no-op
    (the profiler cannot nest).  Emits obs instant events at both edges so
    the obs trace shows where the device profile interleaves."""

    __slots__ = ("_dir", "_name", "_started")

    def __init__(self, log_dir: str, name: str) -> None:
        self._dir = log_dir
        self._name = name
        self._started = False

    def __enter__(self) -> "_ProfileWindow":
        with _PROFILE_LOCK:
            if _PROFILE_ACTIVE["on"]:
                return self
            _PROFILE_ACTIVE["on"] = True
        try:
            import jax.profiler

            os.makedirs(self._dir, exist_ok=True)
            jax.profiler.start_trace(self._dir)
            self._started = True
            event("device/profile_start", name=self._name, dir=self._dir)
        except Exception:
            with _PROFILE_LOCK:
                _PROFILE_ACTIVE["on"] = False
        return self

    def __exit__(self, *exc) -> None:
        if not self._started:
            return
        try:
            import jax.profiler

            jax.profiler.stop_trace()
            event("device/profile_stop", name=self._name)
        except Exception:
            pass
        finally:
            with _PROFILE_LOCK:
                _PROFILE_ACTIVE["on"] = False


class _NoopWindow:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NOOP_WINDOW = _NoopWindow()


def profile_window(name: str):
    """Env-gated device profiler window: ``OBS_JAX_PROFILE=<dir>`` turns the
    returned context manager into a real ``jax.profiler`` capture written
    under ``<dir>``; otherwise it is a shared no-op.  Safe to nest — only
    the outermost window captures."""
    log_dir = os.environ.get("OBS_JAX_PROFILE", "")
    if not log_dir or not enabled():
        return _NOOP_WINDOW
    return _ProfileWindow(log_dir, name)
