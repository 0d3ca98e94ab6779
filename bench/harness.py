"""Run one benchmark cell once and print its result line.

The cell is found by name in ``BENCHMARK.json``; its configuration, traffic
mix, driver, limits and per-layer metrics are found by name under ``bench/``.
A run: set-up (the cell's driver module: data from the seed, the program built and
warmed) -> the measured window (traced by ``jax.profiler`` with
``--trace 1``) -> the device's memory peak -> the correctness comparison
against the plain reference -> metrics.  The last lines of standard error
are the numbers compared, each beside its limit; the last line of standard
output is the result.  A run that finds no TPU, or fewer chips than the cell
asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from . import deploy


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def benchmark() -> Dict:
    return deploy.load_json(deploy.ROOT / "BENCHMARK.json")


def find_cell(bench: Dict, name: str) -> Dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def applies(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    """A metric with ``workloads`` applies to the cells it lists; a
    per-layer metric without it applies wherever the metric it moves is
    reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program however quickly it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(deploy.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def chips(count: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < count:
        raise NoChip(f"the cell asks for {count} chips, found {len(devs)}")
    return devs[:count]


class CompileCounter:
    """Counts XLA compiles while active (JAX's monitoring events): programs
    handed to the backend, less those the persistent cache served."""

    def __init__(self):
        import jax

        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_event(self, event, **_):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.count -= 1


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             overrides: Optional[Dict] = None) -> Dict:
    """One run of one cell; returns the result line as a dict.  Tests pass
    ``require_chip=False`` and ``overrides`` to run it small on the CPU
    (keys of the configuration's groups, and of the traffic mix under
    ``"traffic"``)."""
    from . import trace as trace_mod

    bench = benchmark()
    cell = find_cell(bench, cell_name)
    if require_chip:
        enable_compile_cache()
        devices = chips(cell["chips"])
    else:
        import jax

        devices = jax.devices()[: cell["chips"]]
    peaks = peaks_for(devices[0].device_kind) if trace else None
    overrides = dict(overrides or {})
    traffic_overrides = overrides.pop("traffic", {})
    config = deploy.load_config(cell["config"], overrides)
    traffic = deploy.load_json(deploy.BENCH / "traffic" / f"{cell['traffic']}.json")
    traffic.update(traffic_overrides)
    limits = deploy.load_json(deploy.BENCH / "limits" / f"{cell_name}.json")
    driver = deploy.load_module("drivers", traffic["driver"])
    tmp = tempfile.mkdtemp(prefix="bench_")
    try:
        ctx = deploy.Context(config, traffic, seed)
        state = driver.setup(ctx)
        setup_s = time.perf_counter() - t_start

        compiles = CompileCounter()
        tracer = trace_mod.Tracer(tmp) if trace else None
        if tracer:
            tracer.start()
        compiles.active = True
        # a mix whose device trace overflows the profiler's buffer in a full
        # window traces a shorter one (``trace_seconds``)
        win = driver.window(state, min(seconds, traffic.get("trace_seconds", seconds))
                            if trace else seconds)
        compiles.active = False
        if tracer:
            t_stop = time.perf_counter()
            tracer.stop()
            print(f"bench: trace written in {time.perf_counter() - t_stop:.1f} s",
                  file=sys.stderr)
        if compiles.count:
            print(f"bench: {compiles.count} compiles inside the window", file=sys.stderr)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)

        values = driver.check(state, win)
        for name, value in values.items():
            print(f"reading {name}: {value!r}", file=sys.stderr)
        checks = compare_checks(values, limits)

        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        e2e_defs = [m for m in bench["end_to_end"] if applies(m, cell_name, [])]
        e2e_names = [m["name"] for m in e2e_defs]
        result = {"correct": all(c["ok"] for c in checks),
                  "attempted": int(win["attempted"]), "failed": int(win["failed"])}
        if not trace:
            measured = dict(win["e2e"], setup_s=setup_s)
            result["metrics"] = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                                 for m in e2e_defs}
        else:
            t_reduce = time.perf_counter()
            summary = tracer.reduce(len(devices))
            print(f"bench: trace reduced in {time.perf_counter() - t_reduce:.1f} s",
                  file=sys.stderr)
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            run = Run(win["stats"], summary, peaks)
            result["metrics"] = per_layer(bench, cell_name, e2e_names, run)
            result["breakdown"] = summary.breakdown()
        result["device"] = device
        result["checks"] = {c["name"]: {"value": finite(c["value"]), "limit": c["limit"]}
                            for c in checks}
        return result
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def finite(x: float) -> float:
    """JSON has no infinity: a reading beyond every float prints as the
    largest float."""
    return x if math.isfinite(x) else sys.float_info.max


def compare_checks(values: Dict[str, float], limits: Dict) -> List[Dict]:
    from .reference import compare

    return compare.judge(values, limits["checks"])


@dataclasses.dataclass
class Run:
    """What a per-layer metric reads: the window's stats (the driver module's and
    the program's counters and spans), the reduced trace and the device's
    peaks."""

    stats: Dict
    trace: object
    peaks: Dict


def peaks_for(device_kind: str) -> Dict:
    """The device's peaks from ``peaks.json``; a device not in the table is
    an error, never a default."""
    table = deploy.load_json(deploy.BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def per_layer(bench: Dict, cell_name: str, e2e_names: List[str], run: Run) -> Dict:
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, cell_name, e2e_names):
            continue
        value = deploy.load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
