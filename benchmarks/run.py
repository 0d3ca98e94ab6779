"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig2,table3]

Default (quick) sizes finish on CPU in ~10 minutes; ``--full`` uses the
paper-scale sample counts (up to 2M).  Results go to results/benchmarks.csv.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from . import (
    history,
    bench_ablation,
    bench_bound,
    bench_fit,
    bench_ihb,
    bench_multiclass,
    bench_obs,
    bench_online,
    bench_ordering,
    bench_performance,
    bench_resilience,
    bench_scaling,
    bench_serve,
    bench_solvers,
    bench_streaming,
    bench_transform,
    roofline,
)
from .common import Reporter
from repro.launch import compile_cache

BENCHES = {
    "fig1_bound": bench_bound.run,
    "fig2_solvers": bench_solvers.run,
    "fig3_ihb": bench_ihb.run,
    "fig4_scaling": bench_scaling.run,
    "table1_ordering": bench_ordering.run,
    "table3_performance": bench_performance.run,
    "ablation_psi": bench_ablation.run,
    "transform_fused": bench_transform.run,
    "fit_fused": bench_fit.run,
    "serve_engine": bench_serve.run,
    "multiclass_batched": bench_multiclass.run,
    "streaming_oavi": bench_streaming.run,
    "online_oavi": bench_online.run,
    "resilience_chaos": bench_resilience.run,
    "obs_overhead": bench_obs.run,
    "roofline": roofline.run,
}

# Benchmarks that start child processes which need the accelerator.  They
# run first, before this process initializes a JAX backend: a process that
# has touched JAX holds the chip, and a child that needs it then fails or
# hangs.
SPAWNS_DEVICE_CHILDREN = ("resilience_chaos",)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--out", type=str, default="results/benchmarks.csv")
    args = ap.parse_args(argv)

    names = list(BENCHES) if not args.only else args.only.split(",")
    names.sort(key=lambda name: name not in SPAWNS_DEVICE_CHILDREN)  # stable
    compile_cache.enable()
    rep = Reporter()
    t0 = time.time()
    for name in names:
        if name not in BENCHES:
            print(f"unknown benchmark {name!r}", file=sys.stderr)
            continue
        print(f"=== {name} ===", flush=True)
        t1 = time.time()
        BENCHES[name](rep, quick=not args.full)
        print(f"=== {name} done in {time.time() - t1:.1f}s ===", flush=True)
    rep.write_csv(args.out)
    # one history record per invocation: the perf-regression gate's raw data
    results_dir = os.path.dirname(args.out) or "results"
    history.append_record(
        history.collect_record(results_dir),
        os.path.join(results_dir, "history.jsonl"),
    )
    print(f"all benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
