"""Resilience benchmark: what fault tolerance costs, and how fast recovery is.

Three numbers back the "degrade, don't die" claims:

* **happy-path overhead** — integrity verification (per-shard CRC32 on
  first read, checksummed checkpoint leaves) must cost **<= 5%** wall-clock
  on a warm out-of-core fit (asserted; soft under ``BENCH_SOFT=1``).  CRC32
  is one cheap sequential pass per shard, amortized across every chunk that
  shard feeds.
* **recovery time** — SIGKILL the continuous controller at a journaled
  phase transition, restart it on the same workdir, and report wall-clock
  to a fully caught-up, bit-identical model (asserted identical to an
  uninterrupted run; 0 warm recompiles after the cold catch-up update).
* **degraded-mode serving** — inject an activation failure mid-run; the
  controller keeps serving the last-good version (0 bitwise mismatches,
  asserted) and the report carries the degraded-window serve latency next
  to the clean run's.

Emits ``results/BENCH_resilience.json`` (``bench.v1`` schema).

    PYTHONPATH=src python -m benchmarks.run --only resilience_chaos
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from repro import streaming
from repro.core.oavi import OAVIConfig
from repro.data.synthetic import write_shards
from repro.launch import chaos_vi
from repro.resilience.chaos import Fault, FaultPlan
from repro.streaming.source import ShardDirSource

from .common import Reporter, timeit, write_bench_json

MAX_OVERHEAD = 0.05  # integrity verification budget on the happy path
SHARD_ROWS = 8192
CHUNK_ROWS = 4096


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


def _overhead_row(tmp: str, m: int) -> dict:
    """Warm streaming fit over a shard directory, CRC verification on/off."""
    shard_dir = os.path.join(tmp, f"shards_{m}")
    rng = np.random.default_rng(0)
    X = rng.uniform(0.0, 1.0, (m, 3)).astype(np.float32)
    X[:, 2] = np.clip(X[:, 0] * X[:, 1] + rng.normal(0, 0.01, m), 0, 1).astype(
        np.float32
    )
    write_shards(shard_dir, X, shard_rows=SHARD_ROWS)
    cfg = OAVIConfig(psi=0.005, engine="fast", ordering="pearson", cap_terms=64)

    def fit_with(verify: bool):
        # fresh source each run: per-shard verification is lazy + cached,
        # so a reused source would only pay the CRC on its first pass
        src = ShardDirSource(shard_dir, verify_checksums=verify)
        return streaming.fit(src, cfg, chunk_rows=CHUNK_ROWS)

    fit_with(True)  # warm compile caches both paths share
    t_off = timeit(lambda: fit_with(False), repeat=3)
    t_on = timeit(lambda: fit_with(True), repeat=3)
    overhead = t_on / max(t_off, 1e-9) - 1.0
    return {
        "section": "integrity_overhead",
        "m": m,
        "shard_rows": SHARD_ROWS,
        "chunk_rows": CHUNK_ROWS,
        "t_verify_off_s": round(t_off, 4),
        "t_verify_on_s": round(t_on, 4),
        "overhead_frac": round(overhead, 4),
        "budget_frac": MAX_OVERHEAD,
    }


def run(rep: Reporter, quick: bool = True):
    # The controller subprocesses run before this process touches JAX: a
    # process that has initialized the backend holds the accelerator, and a
    # child that needs it would then fail or hang.
    rows = []

    # ---- recovery time + degraded serving (controller subprocesses) ------
    with tempfile.TemporaryDirectory(prefix="bench_res_ctl_") as tmp:
        # uninterrupted baseline: the bit-identity reference and the clean
        # serve-latency yardstick
        base_dir = os.path.join(tmp, "baseline")
        t_base = time.perf_counter()
        proc = chaos_vi._run_controller(base_dir)
        t_base = time.perf_counter() - t_base
        assert proc.returncode == 0, proc.stderr[-2000:]
        base_rep = chaos_vi._report(base_dir)
        assert base_rep["serve"]["mismatches"] == 0
        reference = chaos_vi._final_leaves(base_dir)

        phases = [("state_saved", 1)] if quick else [
            ("state_saved", 1), ("activated", 1), ("update_start", 2)
        ]
        for phase, at in phases:
            workdir = os.path.join(tmp, f"kill_{phase}_{at}")
            plan = os.path.join(tmp, f"kill_{phase}_{at}.json")
            FaultPlan(
                [Fault(site=f"controller.{phase}", at=at, action="sigkill")]
            ).save(plan)
            proc = chaos_vi._run_controller(workdir, chaos_path=plan)
            assert proc.returncode == -9, (
                f"expected SIGKILL at {phase}#{at}, got {proc.returncode}\n"
                f"{proc.stderr[-2000:]}"
            )
            t_rec = time.perf_counter()
            proc = chaos_vi._run_controller(workdir)
            t_rec = time.perf_counter() - t_rec
            assert proc.returncode == 0, proc.stderr[-2000:]
            krep = chaos_vi._report(workdir)
            assert krep["resume"]["resumed"], "controller did not resume"
            assert krep["warm_recompiles"] == 0, "recovery recompiled warm"
            assert krep["serve"]["mismatches"] == 0
            chaos_vi._assert_bit_identical(
                chaos_vi._final_leaves(workdir), reference,
                f"recovery at {phase}#{at}",
            )
            row = {
                "section": "recovery",
                "killed_at": f"{phase}#{at}",
                "total_rows": krep["total_rows"],
                "state_rows_resumed": krep["resume"]["state_rows"],
                "caught_up_rows": krep["resume"]["caught_up_rows"],
                "t_uninterrupted_s": round(t_base, 3),
                "t_recovery_s": round(t_rec, 3),
                "t_catch_up_s": round(krep["resume"]["time_catch_up"], 3),
                "bit_identical": True,
                "recompiles_warm": krep["warm_recompiles"],
            }
            rows.append(row)
            rep.add("resilience_chaos", **row)

        # degraded-mode: one injected activation failure mid-run
        deg_dir = os.path.join(tmp, "degraded")
        plan = os.path.join(tmp, "degraded.json")
        FaultPlan([Fault(site="registry.activate", at=1, action="raise")]).save(plan)
        proc = chaos_vi._run_controller(deg_dir, chaos_path=plan)
        assert proc.returncode == 0, proc.stderr[-2000:]
        drep = chaos_vi._report(deg_dir)
        assert len(drep["update_failures"]) == 1, "activation fault not recorded"
        assert drep["serve"]["mismatches"] == 0, "degraded window served wrong bits"
        assert drep["health"] == "ok", "controller did not recover"
        chaos_vi._assert_bit_identical(
            chaos_vi._final_leaves(deg_dir), reference, "degraded run"
        )
        row = {
            "section": "degraded_serving",
            "update_failures": len(drep["update_failures"]),
            "health_final": drep["health"],
            "serve_p50_ms_clean": round(base_rep["serve"]["lat_p50_ms"], 3),
            "serve_p50_ms_degraded": round(drep["serve"]["lat_p50_ms"], 3),
            "serve_p99_ms_clean": round(base_rep["serve"]["lat_p99_ms"], 3),
            "serve_p99_ms_degraded": round(drep["serve"]["lat_p99_ms"], 3),
            "mismatches": drep["serve"]["mismatches"],
            "bit_identical": True,
        }
        rows.append(row)
        rep.add("resilience_chaos", **row)

    # ---- happy-path integrity overhead -----------------------------------
    with tempfile.TemporaryDirectory(prefix="bench_res_io_") as tmp:
        for m in [65_536] if quick else [65_536, 262_144]:
            row = _overhead_row(tmp, m)
            rows.append(row)
            rep.add("resilience_chaos", **row)
            _soft_assert(
                row["overhead_frac"] <= MAX_OVERHEAD,
                f"integrity verification overhead {row['overhead_frac']:.1%} "
                f"> {MAX_OVERHEAD:.0%} at m={m} "
                f"(on {row['t_verify_on_s']}s vs off {row['t_verify_off_s']}s)",
            )

    write_bench_json(
        "resilience",
        rows,
        meta={
            "quick": quick,
            "max_overhead_frac": MAX_OVERHEAD,
            "controller_args": chaos_vi.RUN_ARGS,
        },
    )
