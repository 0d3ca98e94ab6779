#!/usr/bin/env python3
"""Chip smoke test: the paper's Algorithm 2 deployment, fit -> serve, on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded fit and engine, four chips

One chip (the default) runs the main path once through the entry points a
user calls, at the paper's own size:

1. **data** — the Appendix C set (``repro.data.synthetic.appendix_c``:
   m = 2,000,000, n = 3, two classes) with the paper's 60/40 split; the
   classifier min-max scales it.
2. **fit** — Algorithm 2 with CGAVI-IHB (``configs.oavi_paper.pipeline``:
   psi = 0.005, tau = 1000, class-batched) through
   ``VanishingIdealClassifier.fit``; then ``api.fit(method="oavi:fast")``
   on one class.  The compiled degree step must hold both Pallas kernels
   (``tpu_custom_call``).
3. **reference** — the same fits on the same chip with ``kernel="jnp"``
   under ``jax.default_matmul_precision("highest")``.  The O terms and the
   leading terms of G must be identical, the coefficients must agree within
   the tolerances below, and test accuracy within 0.5 points.
4. **serve** — ``clf.save(dir)``, then ``repro.launch.serve_vi.main`` loads
   it through the registry, warms the engine and answers a few hundred
   lognormal-sized predict requests through the micro-batcher.  Every
   served prediction must equal ``clf.predict`` on the same rows, with no
   recompile after warm-up.

``--chips 4`` runs only the sharded path and what it is compared with: the
same class-batched fit sharded over a 4-device mesh against the one-chip fit
(same structure, coefficients within tolerance), the sharded serving engine
against the local one (bit-identical outputs), and a check that each device
holds a quarter of the rows of the evaluation matrix A, not all of it.

Timings printed on the way are smoke readings, not benchmark results.  The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only when
every check passed; any failure, or a machine without a TPU, exits non-zero
without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Coefficient agreement with the reference.  The fast engine's coefficients
# are closed-form (they differ only by rounding); CGAVI's come from a convex
# solver stopped at an objective accuracy of 0.01 * psi, so two runs whose
# arithmetic differs in the last bits may stop at nearby but different
# iterates.
COEF_TOL = {"fast": 1e-3, "oracle": 5e-2}  # max |c - c_ref| / max(1, max |c_ref|)
ACCURACY_POINTS = 0.5
KERNELS = ("gram_update_acc", "ihb_update")  # the fit's two Pallas kernels


class SmokeError(RuntimeError):
    """A check of the smoke test failed."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def devices_or_fail(count: int):
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) >= count, f"need {count} TPU devices, found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# Fits and their comparison
# ---------------------------------------------------------------------------


def paper_pipeline(**overrides):
    from repro.configs import oavi_paper

    return dataclasses.replace(oavi_paper.pipeline("cgavi-ihb"), **overrides)


def fit_classifier(Xtr, ytr, **overrides):
    from repro.core.pipeline import VanishingIdealClassifier

    clf = VanishingIdealClassifier(paper_pipeline(**overrides))
    t0 = time.perf_counter()
    clf.fit(Xtr, ytr)
    return clf, time.perf_counter() - t0


def reference_classifier(Xtr, ytr, Xte, yte):
    """The all-jnp, highest-precision fit of the same deployment."""
    import jax

    with jax.default_matmul_precision("highest"):
        clf, secs = fit_classifier(Xtr, ytr, oavi_kw={"kernel": "jnp"})
        acc = clf.score(Xte, yte)
    return clf, secs, acc


def compare_models(models, ref_models, tol: float, label: str) -> float:
    """Same O terms and G leading terms, coefficients within ``tol``.
    Returns the largest relative coefficient difference."""
    import numpy as np

    check(len(models) == len(ref_models), f"{label}: model count differs")
    worst = 0.0
    for c, (m, r) in enumerate(zip(models, ref_models)):
        check(list(m.book.terms) == list(r.book.terms),
              f"{label} class {c}: O terms differ ({m.num_O} vs {r.num_O})")
        check([g.term for g in m.generators] == [g.term for g in r.generators],
              f"{label} class {c}: G leading terms differ ({m.num_G} vs {r.num_G})")
        for g, gr in zip(m.generators, r.generators):
            scale = max(1.0, float(np.max(np.abs(gr.coeffs))))
            diff = float(np.max(np.abs(g.coeffs - gr.coeffs))) / scale
            worst = max(worst, diff)
    check(worst <= tol, f"{label}: coefficients differ by {worst:.3g} > {tol:g}")
    return worst


def describe(models) -> str:
    from repro.core.oavi import pow2_bucket

    parts = []
    for c, m in enumerate(models):
        s = m.stats
        parts.append(
            f"class {c}: |G|={m.num_G} |O|={m.num_O} Lcap={s['Lcap_final']} "
            f"Kcap={max(64, pow2_bucket(max(s['border_sizes'])))} "
            f"borders={s['border_sizes']} compile_s={s['compile_seconds']}"
        )
    return "; ".join(parts)


def compiled_degree_step(cfg, stats, n: int, mesh=None):
    """Compile the class-batched degree step the fit ran, at its last shape
    bucket (the persistent compile cache usually has it already).  Returns
    the compiled executable and the global shape of A."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import class_batch, distributed, ihb as ihb_mod
    from repro.core.oavi import pow2_bucket

    k = stats["class_batch"]["size"]
    mc = stats["class_batch"]["m_cap"]
    Lcap = stats["Lcap_final"]
    Kcap = max(cfg.cap_border, pow2_bucket(max(stats["border_sizes"])))
    entry = class_batch._batched_entry(cfg, mesh, ("data",))
    rows = rep = None
    if mesh is not None:
        rows = NamedSharding(mesh, distributed.class_data_spec(("data",)))
        rep = NamedSharding(mesh, P())

    def sds(shape, dtype=jnp.float32, sharding=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding or rep)

    state = jax.eval_shape(
        lambda: ihb_mod.batch_state(
            ihb_mod.init_state(Lcap, jnp.asarray(1.0, jnp.float32), jnp.float32,
                               factors=cfg.ihb_factors()), k)
    )
    state = jax.tree.map(lambda a: sds(a.shape, a.dtype), state)
    i32 = jnp.int32
    a_shape = (k, mc, Lcap)
    compiled = entry.fn.lower(
        sds(a_shape, sharding=rows), sds((k, mc, n), sharding=rows), state,
        sds((k,), i32), sds((k, Kcap), i32), sds((k, Kcap), i32),
        sds((k, Kcap), jnp.bool_), sds((k,)),
    ).compile()
    return compiled, a_shape


def kernels_in(compiled) -> set:
    """Which of the fit's Pallas kernels appear as Mosaic custom calls."""
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {name for ln in calls for name in KERNELS if name in ln}


# ---------------------------------------------------------------------------
# One chip: fit -> reference -> serve
# ---------------------------------------------------------------------------


def one_chip(args) -> None:
    import jax
    import numpy as np

    from repro import api
    from repro.data import synthetic
    from repro.launch import serve_vi

    t0 = time.perf_counter()
    X, y = synthetic.appendix_c(m=args.m, seed=args.seed)
    Xtr, ytr, Xte, yte = synthetic.train_test_split(X, y, test_frac=0.4, seed=args.seed)
    log(f"data: Appendix C m={args.m} n={X.shape[1]} train={len(ytr)} "
        f"test={len(yte)} in {time.perf_counter() - t0:.2f}s")

    # -- Algorithm 2, CGAVI-IHB, class-batched ------------------------------
    clf, t_fit = fit_classifier(Xtr, ytr)
    acc = clf.score(Xte, yte)
    st = clf.stats
    log(f"fit cgavi-ihb: {t_fit:.2f}s (smoke reading) class_batched="
        f"{st['class_batched']} recompiles={st['recompiles']} accuracy={acc:.4f}")
    log(describe(clf.models))
    cfg = api.oavi_config_for("cgavi-ihb", clf.config.psi)
    kernels = kernels_in(compiled_degree_step(cfg, clf.models[0].stats, X.shape[1])[0])
    log(f"compiled degree step custom calls: {sorted(kernels)}")
    check(set(KERNELS) <= kernels,
          f"degree step lacks a Pallas kernel: {sorted(kernels)}")

    ref, t_ref, acc_ref = reference_classifier(Xtr, ytr, Xte, yte)
    log(f"reference (kernel=jnp, precision=highest): {t_ref:.2f}s accuracy={acc_ref:.4f}")
    diff = compare_models(clf.models, ref.models, COEF_TOL["oracle"], "cgavi-ihb")
    log(f"cgavi-ihb vs reference: structure identical, max coefficient diff {diff:.3g}")
    check(abs(acc - acc_ref) * 100 <= ACCURACY_POINTS,
          f"accuracy {acc:.4f} vs reference {acc_ref:.4f}")

    # -- api.fit, the default fast engine, on one class ----------------------
    Xc = clf.scaler.transform(Xtr[ytr == clf.classes_[0]])
    t1 = time.perf_counter()
    fast = api.fit(Xc, method="oavi:fast", psi=clf.config.psi, backend="local")
    t_fast = time.perf_counter() - t1
    with jax.default_matmul_precision("highest"):
        fast_ref = api.fit(Xc, method="oavi:fast", psi=clf.config.psi,
                           backend="local", kernel="jnp")
    diff = compare_models([fast], [fast_ref], COEF_TOL["fast"], "oavi:fast")
    log(f"api.fit oavi:fast m={Xc.shape[0]}: {t_fast:.2f}s (smoke reading) "
        f"|G|={fast.num_G} |O|={fast.num_O} recompiles={fast.stats['recompiles']}; "
        f"vs reference: structure identical, max coefficient diff {diff:.3g}")

    # -- serve: save -> registry load -> engine -> micro-batcher -------------
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        model_dir = os.path.join(tmp, "clf")
        clf.save(model_dir)
        t2 = time.perf_counter()
        report = serve_vi.main([
            "--model-dir", model_dir, "--kind", "predict",
            "--requests", str(args.requests), "--mean-rows", "128",
            "--concurrency", "8", "--seed", str(args.seed),
        ])
        t_serve = time.perf_counter() - t2
    check(report["kind"] == "predict", f"served {report['kind']!r}, not predict")
    check(report["recompiles"] == 0,
          f"{report['recompiles']} recompiles after warm-up")
    served = np.concatenate(report["outputs"])
    direct = clf.predict(np.concatenate(report["inputs"]))
    mismatched = int(np.sum(served != direct))
    check(mismatched == 0,
          f"{mismatched} of {len(direct)} served predictions differ from clf.predict")
    log(f"serve: {report['requests']} predict requests, {report['rows']} rows, "
        f"p50 {report['lat_p50_ms']:.2f}ms p99 {report['lat_p99_ms']:.2f}ms, "
        f"{t_serve:.2f}s with load and warm-up (smoke reading); "
        f"predictions equal clf.predict")


# ---------------------------------------------------------------------------
# Four chips: sharded fit and sharded engine against their one-chip twins
# ---------------------------------------------------------------------------


def four_chips(args, devs) -> None:
    import jax
    import numpy as np

    from repro import api
    from repro.data import synthetic
    from repro.serving import TransformEngine

    mesh = jax.make_mesh((4,), ("data",), devices=devs[:4])
    X, y = synthetic.appendix_c(m=args.m, seed=args.seed)
    Xtr, ytr, Xte, yte = synthetic.train_test_split(X, y, test_frac=0.4, seed=args.seed)

    clf, t_fit = fit_classifier(Xtr, ytr, backend="sharded", mesh=mesh)
    log(f"sharded fit on 4 chips: {t_fit:.2f}s (smoke reading); {describe(clf.models)}")
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devs[:4]]
    cfg = api.oavi_config_for("cgavi-ihb", clf.config.psi)
    compiled, a_shape = compiled_degree_step(cfg, clf.models[0].stats, X.shape[1], mesh)
    a_in = compiled.input_shardings[0][0].shard_shape(a_shape)
    a_out = compiled.output_shardings[0].shard_shape(a_shape)
    quarter = (a_shape[0], a_shape[1] // 4, a_shape[2])
    log(f"A {a_shape}: each device holds {a_in} in and {a_out} out of the sharded "
        f"degree step; kernels {sorted(kernels_in(compiled))}; "
        f"peak bytes in use per device {peaks}")
    check(a_in == quarter and a_out == quarter,
          f"A is not split in quarters over the rows: {a_in} / {a_out}")
    check(set(KERNELS) <= kernels_in(compiled),
          "sharded degree step lacks a Pallas kernel")

    local, t_local = fit_classifier(Xtr, ytr, backend="local")
    log(f"one-chip fit: {t_local:.2f}s (smoke reading)")
    diff = compare_models(clf.models, local.models, COEF_TOL["oracle"], "sharded fit")
    log(f"sharded vs one-chip fit: structure identical, max coefficient diff {diff:.3g}")

    Z = clf.scaler.transform(Xte)
    sharded = TransformEngine(clf.models, mesh=mesh)
    single = TransformEngine(clf.models)
    sharded.warmup()
    single.warmup()
    rng = np.random.default_rng(args.seed)
    sizes = [int(s) for s in rng.lognormal(np.log(128), 0.9, args.requests // 4)]
    start = 0
    for q in sizes:
        rows = Z[start:start + q]
        start += q
        a, b = sharded.transform(rows), single.transform(rows)
        check(np.array_equal(a, b), f"sharded engine differs from local at q={q}")
    check(sharded.stats["recompiles"] == 0, "sharded engine recompiled after warm-up")
    log(f"sharded engine ({sharded.shards} shards) == local engine, bit for bit, "
        f"over {len(sizes)} requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--m", type=int, default=2_000_000,
                    help="Appendix C sample count (the paper's is 2,000,000)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=300)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    compile_cache.enable()
    try:
        devs = devices_or_fail(args.chips)
        if args.chips == 4:
            four_chips(args, devs)
        else:
            one_chip(args)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
