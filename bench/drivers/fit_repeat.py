"""Driver ``fit_repeat``: whole Algorithm 2 fits, back to back.

The window's inputs are the seed's training split, or, where the traffic mix
lists ``order_seeds``, one input per order seed (the configuration's fixed
data set, its rows in that seed's order), the same for every ``--seed``:
where the order of the rows alone changes the fit's work (the oracle's
escalations follow rounding), every run then does the same work, and
``--seed`` draws the order in which the window cycles through the inputs.
Set-up fits each input once, which compiles (or loads from the compile
cache) every program the window's fits use.  The window calls
``VanishingIdealClassifier.fit`` on the inputs in turn, and closes at the
end of the first whole cycle through them that ends after ``--seconds``; it
holds whole fits only.  ``fit_s`` is the window's length over the fits it
holds.

Correctness: one fit of the window, drawn from the seed, against the plain
reference fitted to the same rows.  ``check`` reads the classes' feature
orders, O terms and generator leading terms (fit driver, oracle, both
kernels), the generators' mean squared evaluations (as reported, and
evaluated anew by the reference) and coefficients, the transformed features
of the seed's test split (transform), and the SVM's scores and labels there
and its objective on the training rows (pipeline head); the cell's limits
name the ones compared (``PERF.md`` says why the others are not).
"""

from __future__ import annotations

import sys
import time
from typing import Dict

import numpy as np

from bench import deploy
from bench.reference import algorithm2, compare


def setup(ctx: deploy.Context) -> Dict:
    seeds = ctx.traffic.get("order_seeds") or [ctx.seed]
    inputs = [deploy.make_data(ctx.config, s)[:3] for s in seeds]
    cycle = [int(k) for k in np.random.default_rng(ctx.seed).permutation(len(inputs))]
    for Xtr, ytr, _ in inputs:  # warm-up: every shape of every input
        deploy.program_classifier(ctx.config).fit(Xtr, ytr)
    return {"ctx": ctx, "inputs": inputs, "cycle": cycle}


def window(state: Dict, seconds: float) -> Dict:
    ctx, inputs, cycle = state["ctx"], state["inputs"], state["cycle"]
    fits, failed = [], 0
    t0 = time.perf_counter()
    i = 0
    while True:
        k = cycle[i % len(cycle)]
        Xtr, ytr, _ = inputs[k]
        clf = deploy.program_classifier(ctx.config)
        try:
            clf.fit(Xtr, ytr)
            fits.append((k, clf))
        except Exception as e:  # counted, reported, and fails the check
            failed += 1
            print(f"bench: fit failed: {e!r}", flush=True)
        i += 1
        if i % len(cycle) == 0 and time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    done = len(fits)
    stats = [fit_stats(c) for _, c in fits]
    for k in range(len(inputs)):
        st = [f for (j, _), f in zip(fits, stats) if j == k]
        if st:
            times = [f["time_total"] for f in st]
            print(f"bench: input {k}: {len(st)} fits, {min(times):.4f}-{max(times):.4f} s, "
                  f"escalations {sorted({f['solver_escalations'] for f in st})}",
                  file=sys.stderr)
    return {
        "attempted": done + failed,
        "failed": failed,
        "e2e": {"fit_s": wall / max(done, 1)},
        "stats": {"fits": stats, "wall_s": wall},
        "fits": fits,
    }


def fit_stats(clf) -> Dict:
    """The program's own spans and counters of one fit, and the per-degree
    sizes that the kernels' work is counted from."""
    s = clf.stats
    m0 = clf.models[0].stats
    classes = []
    for m in clf.models:
        st = m.stats
        deg_of = [sum(t) for t in m.book.terms]
        classes.append({
            "m": int(st["m"]),
            "n": int(st["n"]),
            "degrees": list(st["degrees"]),
            "border_sizes": list(st["border_sizes"]),
            "O_per_degree": [int(sum(1 for dg in deg_of if dg == d)) for d in st["degrees"]],
        })
    return {
        "time_total": s["time_total"],
        "time_generators": s["time_generators"],
        "time_transform": s["time_transform"],
        "time_svm": s["time_svm"],
        "degree_times": list(m0["degree_times"]),
        "time_unattributed": m0.get("time_unattributed", 0.0),
        "solver_escalations": s["solver_escalations"],
        "svm_iters": int(s["svm"]["iters"]),
        "classes": classes,
    }


def program_answers(clf, Xte) -> Dict:
    F = np.asarray(clf.transform(Xte))
    return {"models": deploy.program_models(clf), "features": F,
            "W": np.asarray(clf.svm.W), "b": np.asarray(clf.svm.b),
            "scores": np.asarray(clf.svm.decision_function(F)),
            "labels": np.asarray(clf.svm.predict(F))}


def reference_values(answers: Dict, ref: algorithm2.Reference, data) -> Dict[str, float]:
    """The numbers compared, for any answers against the reference;
    ``data`` is the seed's ``(Xtr, ytr, Xte)``."""
    Xtr, ytr, Xte = data
    Ztr = algorithm2.minmax_apply(Xtr, ref.lo, ref.scale)
    bad = compare.structure_mismatches(answers["models"], ref.models)
    bad += sum(list(p["perm"]) != [int(i) for i in r.perm]
               for p, r in zip(answers["models"], ref.models))
    Zte = algorithm2.minmax_apply(Xte, ref.lo, ref.scale)
    F_ref = ref.features_scaled(Zte)
    scores = algorithm2.mm(F_ref, ref.W, ref.precision) + ref.b
    inf = float("inf")
    psi = ref.psi
    return {
        "structure": float(bad),
        "mse_gap": compare.mse_gap(answers["models"], ref.models, psi) if not bad else inf,
        "vanish_gap": vanish_gap(answers["models"], ref, Ztr, ytr) if not bad else inf,
        "svm_excess": svm_excess(answers, ref, Ztr, ytr) if not bad else inf,
        "coef_gap": compare.coefficient_gap(answers["models"], ref.models) if not bad else inf,
        "feature_gap": compare.column_gap(answers["features"], F_ref) if not bad else inf,
        "score_gap": compare.column_gap(answers["scores"], scores) if not bad else inf,
        "label_gap": compare.label_gap(answers["labels"], scores, ref.classes),
    }


def vanish_gap(models, ref: algorithm2.Reference, Ztr, ytr) -> float:
    """Widest difference, over psi, between the mean squared evaluation of a
    generator with the answered coefficients and with the reference's, both
    evaluated by the reference in float64 over the class's training rows:
    what OAVI promises of the generators it returns (a reported MSE can be
    right while the coefficients returned beside it are not)."""
    worst = 0.0
    for p, r, c in zip(models, ref.models, ref.classes):
        Zc = Ztr[np.asarray(ytr) == c]
        theirs = algorithm2.generator_mse(r, Zc)
        mine = algorithm2.generator_mse(r, Zc, p["gen_coeffs"])
        worst = max(worst, float(np.max(np.abs(mine - theirs), initial=0.0)) / ref.psi)
    return worst


def svm_excess(answers: Dict, ref: algorithm2.Reference, Ztr, ytr) -> float:
    """How much worse the answered SVM head does than the reference's at the
    objective both were set (mean squared hinge over the training rows plus
    lam |W|_1), each evaluated by the reference in float64 on its own
    features of those rows, relative to the reference's value.  Unlike the
    scores, which an SVM stopped at its iteration cap moves by rounding,
    the objective is flat at the optimum and steady from seed to seed."""
    F = ref.features_scaled(Ztr)
    Y = np.where(np.asarray(ytr)[:, None] == ref.classes[None, :], 1.0, -1.0)

    def objective(W, b):
        W = np.asarray(W, np.float64)
        active = np.maximum(1.0 - Y * (F @ W + np.asarray(b, np.float64)), 0.0)
        return float(np.mean(np.sum(active * active, axis=1)) + ref.lam * np.sum(np.abs(W)))

    best = objective(ref.W, ref.b)
    return (objective(answers["W"], answers["b"]) - best) / best


def check(state: Dict, win: Dict) -> Dict[str, float]:
    ctx = state["ctx"]
    fits = win.pop("fits")
    if not fits:
        return {"fits_failed": float(win["failed"] or 1)}
    pick = int(np.random.default_rng(ctx.seed).integers(len(fits)))
    k, clf = fits[pick]
    Xtr, ytr, Xte = state["inputs"][k]
    answers = program_answers(clf, Xte)
    del fits, clf
    ref = algorithm2.fit(Xtr, ytr, ctx.config["method"], ctx.config["svm"], "highest")
    values = reference_values(answers, ref, (Xtr, ytr, Xte))
    values["fits_failed"] = float(win["failed"])
    return values


def control(ctx: deploy.Context, ref, low, data) -> Dict[str, float]:
    """The control: the reference at the precision below the configuration's
    (three bf16 passes, ``low``) in the program's place, against the
    reference ``ref``, both fitted to the seed's training split; ``data`` is
    the seed's ``(Xtr, ytr, Xte)``."""
    Xte = data[2]
    Z = algorithm2.minmax_apply(Xte, low.lo, low.scale)
    F = low.features_scaled(Z)
    answers = {
        "models": [{"perm": [int(i) for i in m.perm], "terms": m.terms,
                    "gen_terms": m.gen_terms, "gen_coeffs": m.gen_coeffs,
                    "gen_mse": m.gen_mse}
                   for m in low.models],
        "features": F,
        "W": low.W, "b": low.b,
        "scores": algorithm2.mm(F, low.W, "high") + low.b,
    }
    answers["labels"] = low.classes[np.argmax(answers["scores"], axis=1)]
    return reference_values(answers, ref, data)
