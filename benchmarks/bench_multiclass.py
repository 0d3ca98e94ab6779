"""Multi-class fit benchmark: sequential per-class OAVI vs the class-batched
(vmapped) path.

Measures, at k in {4, 8, 16} classes on synthetic planted-variety data:

* **equal class sizes** (pow2 rows, no padding) — end-to-end multi-class
  generator-fit wall clock, sequential loop of :func:`repro.core.oavi.fit`
  vs :func:`repro.core.class_batch.fit_classes`.  The batched result is
  asserted **bit-exact** against the sequential fits (no row padding, so
  matched capacity holds automatically), and the k=8 row must show the
  >= 2x speedup the class-batched path is for.
* **lognormal-skewed class sizes** — the realistic regime: classes are
  grouped into shared row buckets by :func:`repro.api.fit_classes`
  (cross-bucket merges bounded ~2x padding; stragglers folded into their
  cheapest warm bucket, never sequential); speedup plus padding overhead
  and the group count are reported.  Structure (terms, accepted
  generators) is asserted identical to the sequential fits.
* **bpcg oracle engine** (equal sizes) — the paper's BPCG+IHB config with
  its solvers under the class vmap: batched asserted bit-exact against the
  sequential fits, >= 2x at k=8, 0 warm recompiles.
* **warm-refit recompiles** — a second batched multi-class fit must report
  0 recompiles (shared global degree-step cache).

Emits ``results/BENCH_multiclass.json`` (``bench.v1`` schema).

    PYTHONPATH=src python -m benchmarks.run --only multiclass_batched
"""

from __future__ import annotations

import os

import jax
import numpy as np

from repro import api
from repro.core import class_batch, oavi
from repro.core.oavi import OAVIConfig
from repro.core.oracles import OracleConfig
from repro.core.transform import MinMaxScaler
from repro.data.synthetic import lognormal_sizes, multiclass_planted

from .common import Reporter, timeit, write_bench_json

PSI = 0.005
N_FEATURES = 4


def _per_class(X, y):
    classes = np.unique(y)
    return [X[y == c] for c in classes]


def _assert_bit_exact(seq, bat):
    for s, b in zip(seq, bat):
        assert s.book.terms == b.book.terms, "term books differ"
        assert [g.term for g in s.generators] == [g.term for g in b.generators]
        for gs, gb in zip(s.generators, b.generators):
            assert np.array_equal(gs.coeffs, gb.coeffs), f"coeffs differ {gs.term}"
            assert gs.mse == gb.mse


def _assert_structure(seq, bat):
    for s, b in zip(seq, bat):
        assert s.book.terms == b.book.terms, "term books differ"
        assert [g.term for g in s.generators] == [g.term for g in b.generators]


def run(rep: Reporter, quick: bool = True):
    cfg = OAVIConfig(psi=PSI, engine="fast", cap_terms=64)
    ks = [4, 8, 16]
    # 512 rows/class quick: the dispatch-bound regime the batched path is
    # for (UCI-scale classes), and the widest measured speedup margin
    mean_rows = 512 if quick else 4096
    rows = []

    for k in ks:
        # ---- equal sizes (pow2 -> padding-free -> bit-exact) -------------
        X, y = multiclass_planted([mean_rows] * k, n=N_FEATURES, seed=k)
        X = MinMaxScaler(dtype="float32").fit_transform(X)
        Xcs = _per_class(X, y)

        seq0 = [oavi.fit(Xc, cfg) for Xc in Xcs]  # warm both paths
        bat0 = class_batch.fit_classes(Xcs, cfg)
        _assert_bit_exact(seq0, bat0)

        t_seq = timeit(lambda: [oavi.fit(Xc, cfg) for Xc in Xcs], repeat=5)
        t_bat = timeit(lambda: class_batch.fit_classes(Xcs, cfg), repeat=5)
        warm = class_batch.fit_classes(Xcs, cfg)
        speedup = t_seq / max(t_bat, 1e-9)
        row = {
            "section": "equal_sizes",
            "k": k,
            "rows_per_class": mean_rows,
            "n": N_FEATURES,
            "num_G_total": sum(m.num_G for m in bat0),
            "t_sequential_s": round(t_seq, 4),
            "t_batched_s": round(t_bat, 4),
            "speedup": round(speedup, 2),
            "bit_exact": True,
            "recompiles_warm": warm[0].stats["recompiles"],
        }
        rows.append(row)
        rep.add("multiclass_batched", **row)
        assert warm[0].stats["recompiles"] == 0, "warm batched refit recompiled"
        if k == 8 and speedup < 2.0:
            # wall-clock guard: hard failure locally, soft on constrained
            # CI runners (BENCH_SOFT=1: noisy 2-vCPU machines miss timing
            # targets without anything being wrong with the code)
            msg = f"k=8 equal-size class-batched speedup {speedup:.2f}x < 2x"
            if os.environ.get("BENCH_SOFT"):
                print(f"WARNING: {msg} (BENCH_SOFT set; not failing)")
            else:
                raise AssertionError(msg)

        # ---- bpcg oracle engine (its solvers under vmap) ----------------
        cfg_bpcg = OAVIConfig(
            psi=PSI,
            engine="oracle",
            solver=OracleConfig(name="bpcg"),
            ihb=True,
            cap_terms=64,
        )
        seq0 = [oavi.fit(Xc, cfg_bpcg) for Xc in Xcs]
        bat0 = class_batch.fit_classes(Xcs, cfg_bpcg)
        _assert_bit_exact(seq0, bat0)

        t_seq = timeit(lambda: [oavi.fit(Xc, cfg_bpcg) for Xc in Xcs], repeat=5)
        t_bat = timeit(lambda: class_batch.fit_classes(Xcs, cfg_bpcg), repeat=5)
        warm = class_batch.fit_classes(Xcs, cfg_bpcg)
        speedup = t_seq / max(t_bat, 1e-9)
        row = {
            "section": "bpcg_oracle",
            "k": k,
            "rows_per_class": mean_rows,
            "n": N_FEATURES,
            "num_G_total": sum(m.num_G for m in bat0),
            "t_sequential_s": round(t_seq, 4),
            "t_batched_s": round(t_bat, 4),
            "speedup": round(speedup, 2),
            "bit_exact": True,
            "recompiles_warm": warm[0].stats["recompiles"],
        }
        rows.append(row)
        rep.add("multiclass_batched", **row)
        assert warm[0].stats["recompiles"] == 0, "warm bpcg batched refit recompiled"
        if k == 8 and speedup < 2.0:
            msg = f"k=8 bpcg class-batched speedup {speedup:.2f}x < 2x"
            if os.environ.get("BENCH_SOFT"):
                print(f"WARNING: {msg} (BENCH_SOFT set; not failing)")
            else:
                raise AssertionError(msg)

        # ---- lognormal-skewed sizes (bucketed, stragglers folded in) -----
        sizes = lognormal_sizes(k, mean_rows, seed=k)
        Xs, ys = multiclass_planted(sizes, n=N_FEATURES, seed=100 + k)
        Xs = MinMaxScaler(dtype="float32").fit_transform(Xs)
        Xcs = _per_class(Xs, ys)

        seq0 = [oavi.fit(Xc, cfg) for Xc in Xcs]
        bat0 = api.fit_classes(Xcs, "oavi:fast", psi=PSI, cap_terms=64)
        _assert_structure(seq0, bat0)

        t_seq = timeit(lambda: [oavi.fit(Xc, cfg) for Xc in Xcs], repeat=5)
        t_bat = timeit(
            lambda: api.fit_classes(Xcs, "oavi:fast", psi=PSI, cap_terms=64),
            repeat=5,
        )
        agg = api.aggregate_fit_stats(bat0)
        padded_rows = sum(
            m.stats["class_batch"]["m_cap"]
            for m in bat0
            if m.stats.get("class_batch")
        )
        batched_real = sum(
            m.stats["m"] for m in bat0 if m.stats.get("class_batch")
        )
        row = {
            "section": "lognormal_sizes",
            "k": k,
            "sizes": sizes,
            "t_sequential_s": round(t_seq, 4),
            "t_batched_s": round(t_bat, 4),
            "speedup": round(t_seq / max(t_bat, 1e-9), 2),
            "classes_batched": agg["class_batched"],
            "classes_sequential": k - agg["class_batched"],
            "batch_groups": agg["class_batch_groups"],
            "padding_overhead": round(padded_rows / max(batched_real, 1), 3),
            "structure_exact": True,
        }
        rows.append(row)
        rep.add("multiclass_batched", **{k_: v for k_, v in row.items() if k_ != "sizes"})

    write_bench_json(
        "multiclass",
        rows,
        meta={
            "psi": PSI,
            "engine": "fast",
            "mean_rows": mean_rows,
            "quick": quick,
            "backend": jax.default_backend(),
        },
    )
