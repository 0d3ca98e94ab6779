"""Observability benchmark: what instrumentation costs, and what it gets right.

Backs the obs subsystem's two contracts:

* **overhead** — spans/trace/metrics enabled vs disabled on a *warm* fit and
  a *warm* serving replay must cost **<= 3%** wall-clock (asserted; soft
  under ``BENCH_SOFT=1`` on noisy shared runners), and enabling obs must not
  change a single output bit (asserted hard, both paths: fitted generators
  and served feature blocks).
* **fidelity** — the log-bucket histogram sketch's p50/p90/p99/p999 must
  land within one bucket (relative error ``2^(1/16) - 1`` ~ 4.4%) of
  ``np.percentile`` on lognormal and Pareto (heavy-tail) samples, at a few
  hundred bytes of state instead of storing every sample.

Also reports the cost of draining the trace ring buffer to Chrome-trace
JSON (events, seconds, bytes) — the number that says exporting is safe to do
inline at the end of a run.

Emits ``results/BENCH_obs.json`` (``bench.v1`` schema).

    PYTHONPATH=src python -m benchmarks.run --only obs_overhead
"""

from __future__ import annotations

import json
import os
import tempfile
import time

import jax
import numpy as np

from repro import obs
from repro.core import oavi
from repro.core.oavi import OAVIConfig
from repro.core.transform import MinMaxScaler
from repro.data.synthetic import appendix_c
from repro.serving import EngineConfig, TransformEngine

from .common import Reporter, write_bench_json

OVERHEAD_BUDGET = 0.03  # enabled-vs-disabled wall-clock ceiling (fractional)


def _paired_overhead(fn_base, fn_test, repeat: int):
    """Estimate the fractional overhead of ``fn_test`` over ``fn_base``.

    Per-trial wall-clock noise on these workloads is several percent --
    far above the few-microsecond delta this benchmark exists to measure
    -- and almost entirely one-sided (scheduler preemption, allocator
    stalls: trials only ever get *slower*).  The best-of-N time on each
    side is therefore the low-variance estimate of its true floor, and
    the overhead is the ratio of the floors.  Trials alternate order so
    machine drift hits both sides equally, and GC is paused so a
    collection landing inside one window can't masquerade as obs cost.

    Returns ``(best_base, best_test, overhead_frac)``.
    """
    import gc

    best_base = best_test = float("inf")
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for i in range(repeat):
            first, second = (fn_base, fn_test) if i % 2 == 0 else (fn_test, fn_base)
            t0 = time.perf_counter()
            first()
            t_first = time.perf_counter() - t0
            t0 = time.perf_counter()
            second()
            t_second = time.perf_counter() - t0
            t_base, t_test = (t_first, t_second) if i % 2 == 0 else (t_second, t_first)
            best_base = min(best_base, t_base)
            best_test = min(best_test, t_test)
    finally:
        if gc_was_enabled:
            gc.enable()
    overhead = best_test / max(best_base, 1e-9) - 1.0
    return best_base, best_test, overhead


def _measured_overhead(fn_base, fn_test, repeat: int):
    """``_paired_overhead`` with two escapes against machine noise.

    When the first estimate lands over budget, re-measure with three times
    the trials before believing it.  If it is *still* over budget, run a
    control: the same estimator on ``fn_base`` vs ``fn_base``, whose true
    overhead is exactly zero — anything it reads is the measurement noise
    floor of this machine right now.  A hard failure is only meaningful
    when that floor sits well under the budget; otherwise the box (small
    VM, steal time, shared runner) cannot resolve a 3% effect at all and
    the caller downgrades to a warning, same as ``BENCH_SOFT``.

    Returns ``(best_base, best_test, overhead_frac, noise_frac)`` where
    ``noise_frac`` is ``None`` unless the control was run.
    """
    t_base, t_test, overhead = _paired_overhead(fn_base, fn_test, repeat)
    if overhead <= OVERHEAD_BUDGET:
        return t_base, t_test, overhead, None
    t_base, t_test, overhead = _paired_overhead(fn_base, fn_test, 3 * repeat)
    if overhead <= OVERHEAD_BUDGET:
        return t_base, t_test, overhead, None
    _, _, control = _paired_overhead(fn_base, fn_base, repeat)
    return t_base, t_test, overhead, abs(control)


def _assert_overhead(overhead, noise, what: str) -> None:
    if noise is not None and noise > OVERHEAD_BUDGET / 2:
        print(
            f"WARNING: obs overhead on {what} measured {overhead:.1%}, but the "
            f"zero-overhead control measured {noise:.1%} — this machine cannot "
            f"resolve the {OVERHEAD_BUDGET:.0%} budget; not failing"
        )
        return
    _soft_assert(
        overhead <= OVERHEAD_BUDGET,
        f"obs overhead on {what} is {overhead:.1%} (> {OVERHEAD_BUDGET:.0%})",
    )


def _soft_assert(ok: bool, msg: str) -> None:
    """Wall-clock guard: hard failure locally, soft on constrained CI
    runners (BENCH_SOFT=1: noisy 2-vCPU machines miss timing targets
    without anything being wrong with the code)."""
    if ok:
        return
    if os.environ.get("BENCH_SOFT"):
        print(f"WARNING: {msg} (BENCH_SOFT set; not failing)")
    else:
        raise AssertionError(msg)


def _assert_bit_exact(a: oavi.OAVIModel, b: oavi.OAVIModel) -> None:
    assert a.book.terms == b.book.terms, "term books differ"
    assert [g.term for g in a.generators] == [g.term for g in b.generators]
    for ga, gb in zip(a.generators, b.generators):
        assert np.array_equal(ga.coeffs, gb.coeffs), f"coeffs differ for {ga.term}"
        assert ga.mse == gb.mse, f"mse differs for {ga.term}"


def _fit_overhead_row(m: int, repeat: int) -> dict:
    X, _ = appendix_c(m=m, seed=0)
    X = MinMaxScaler(dtype="float32").fit_transform(X)
    cfg = OAVIConfig(psi=0.005, engine="fast")

    # warm both states; the warm-up outputs carry the bit-identity assert
    model_on = oavi.fit(X, cfg)
    with obs.disabled():
        model_off = oavi.fit(X, cfg)
    _assert_bit_exact(model_on, model_off)

    def fit_off():
        with obs.disabled():
            oavi.fit(X, cfg)

    t_off, t_on, overhead, noise = _measured_overhead(
        fit_off, lambda: oavi.fit(X, cfg), repeat
    )
    _assert_overhead(overhead, noise, "warm fit")
    row = {
        "section": "fit_overhead",
        "m": m,
        "t_fit_obs_off_s": round(t_off, 4),
        "t_fit_obs_on_s": round(t_on, 4),
        "overhead_frac": round(overhead, 4),
        "bit_identical": True,
    }
    if noise is not None:
        row["noise_frac"] = round(noise, 4)
    return row, model_on


def _serve_overhead_row(model: oavi.OAVIModel, repeat: int):
    eng = TransformEngine([model], config=EngineConfig(min_bucket=64, max_bucket=4096))
    eng.warmup()
    rng = np.random.default_rng(3)
    sizes = [int(s) for s in np.clip(rng.lognormal(np.log(256), 0.9, 128), 1, 4096)]
    pool, _ = appendix_c(m=max(sizes), seed=1)
    pool = MinMaxScaler(dtype="float32").fit_transform(pool)
    payloads = []
    for q in sizes:
        take = rng.integers(0, pool.shape[0] - q + 1)
        payloads.append(pool[take : take + q])

    out_on = eng.transform(payloads[0])
    with obs.disabled():
        out_off = eng.transform(payloads[0])
    assert np.array_equal(out_on, out_off), "served features differ with obs on"

    def replay():
        for p in payloads:
            eng.transform(p)

    def replay_off():
        with obs.disabled():
            replay()

    t_off, t_on, overhead, noise = _measured_overhead(replay_off, replay, repeat)
    _assert_overhead(overhead, noise, "warm serving")
    row = {
        "section": "serve_overhead",
        "requests": len(payloads),
        "rows": int(sum(p.shape[0] for p in payloads)),
        "t_replay_obs_off_s": round(t_off, 4),
        "t_replay_obs_on_s": round(t_on, 4),
        "overhead_frac": round(overhead, 4),
        "bit_identical": True,
    }
    if noise is not None:
        row["noise_frac"] = round(noise, 4)
    return row, eng


def _export_cost_row() -> dict:
    """Drain whatever the overhead sections buffered into Chrome-trace JSON."""
    events = len(obs.trace_events())
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "trace.json")
        t0 = time.perf_counter()
        obs.export_trace(path)
        t_export = time.perf_counter() - t0
        size = os.path.getsize(path)
        with open(path) as f:
            doc_events = obs.validate_chrome_trace(json.load(f))
    assert len(doc_events) == events, "export dropped or invented events"
    return {
        "section": "trace_export",
        "events": events,
        "t_export_s": round(t_export, 4),
        "bytes": size,
        "valid_chrome_trace": True,
    }


def _device_rows(model: oavi.OAVIModel, eng: TransformEngine) -> list:
    """What the device-level flight recorder costs, and what it recorded.

    The fit/serve overhead sections above already price the *whole* obs
    stack against the disabled path; these rows break out the per-boundary
    memory sampling and assert the stats contract (every fit/serve stats
    dict carries its compile accounting).
    """
    from repro.obs import device as obs_device

    # memory-timeline sampling: the per-degree/chunk-boundary price
    mem_stats: dict = {}
    n_samples = 200
    t0 = time.perf_counter()
    for _ in range(n_samples):
        obs_device.sample_memory(mem_stats)
    t_sample = (time.perf_counter() - t0) / n_samples
    assert "compile_seconds" in model.stats, "fit stats lost compile_seconds"
    eng_stats = eng.stats
    assert "compile_seconds" in eng_stats, "engine stats lost compile_seconds"
    return [
        {
            "section": "device",
            "metric": "memory_sample",
            "calls": n_samples,
            "mean_sample_us": round(t_sample * 1e6, 2),
            "live_bytes_peak": int(mem_stats.get("live_bytes_peak") or 0),
        },
        {
            "section": "device",
            "metric": "stats_contract",
            "fit_compile_seconds": float(model.stats["compile_seconds"]),
            "serve_compile_seconds": float(eng_stats["compile_seconds"]),
        },
    ]


def _sketch_rows() -> list:
    """Sketch quantiles vs np.percentile on lognormal and heavy-tail samples."""
    budget = obs.bucket_relative_error()  # one log-bucket of relative error
    rng = np.random.default_rng(0)
    samples = {
        "lognormal": rng.lognormal(mean=0.0, sigma=1.5, size=200_000),
        "pareto": rng.pareto(a=1.5, size=200_000) + 1.0,
    }
    rows = []
    for name, vals in samples.items():
        h = obs.Histogram()
        h.observe_many(vals)
        worst = 0.0
        per_q = {}
        for q in (50.0, 90.0, 99.0, 99.9):
            exact = float(np.percentile(vals, q))
            approx = h.quantile(q / 100.0)
            rel = abs(approx - exact) / exact
            per_q[f"p{q:g}_rel_err"] = round(rel, 5)
            worst = max(worst, rel)
        assert worst <= budget, (
            f"{name}: sketch quantile off by {worst:.2%} (> one bucket, {budget:.2%})"
        )
        rows.append({
            "section": "sketch_accuracy",
            "distribution": name,
            "samples": int(vals.size),
            "sketch_buckets": h.num_buckets,
            "rel_err_budget": round(budget, 5),
            "worst_rel_err": round(worst, 5),
            **per_q,
        })
    return rows


def run(rep: Reporter, quick: bool = True):
    m = 50_000 if quick else 200_000
    repeat = 7 if quick else 9
    obs.configure(enabled=True, sample_every=1)
    obs.reset()

    fit_row, model = _fit_overhead_row(m, repeat)
    serve_row, eng = _serve_overhead_row(model, repeat)
    export_row = _export_cost_row()
    rows = [fit_row, serve_row, export_row] + _device_rows(model, eng) + _sketch_rows()
    for row in rows:
        rep.add("obs_overhead", **row)

    write_bench_json(
        "obs",
        rows,
        meta={
            "overhead_budget": OVERHEAD_BUDGET,
            "buckets_per_octave": obs.BUCKETS_PER_OCTAVE,
            "quick": quick,
            "backend": jax.default_backend(),
        },
    )


if __name__ == "__main__":
    run(Reporter())
