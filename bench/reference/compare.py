"""The numbers that decide ``correct``: the program's answers against the
plain reference's, each a widest gap.

Adapted from ``chip_smoke.py`` (``compare_models``: same O terms, same
leading terms of G, coefficient difference over ``max(1, max |c_ref|)``),
extended to the transformed features and the SVM's predictions, which a
lower precision moves long before it flips a term (see ``PERF.md``).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def structure_mismatches(prog_models: Sequence[Dict], ref_models: Sequence) -> int:
    """Classes whose O terms or generator leading terms differ (in order).
    ``prog_models``: per class ``{"terms": [...], "gen_terms": [...]}``."""
    if len(prog_models) != len(ref_models):
        return max(len(prog_models), len(ref_models))
    bad = 0
    for p, r in zip(prog_models, ref_models):
        if [tuple(t) for t in p["terms"]] != [tuple(t) for t in r.terms]:
            bad += 1
        elif [tuple(t) for t in p["gen_terms"]] != [tuple(t) for t in r.gen_terms]:
            bad += 1
    return bad


def coefficient_gap(prog_models: Sequence[Dict], ref_models: Sequence) -> float:
    """Widest coefficient difference over all generators, each over
    ``max(1, max |c_ref|)``.  Only meaningful when the structures agree."""
    worst = 0.0
    for p, r in zip(prog_models, ref_models):
        for c, cr in zip(p["gen_coeffs"], r.gen_coeffs):
            c = np.asarray(c, np.float64)
            cr = np.asarray(cr, np.float64)
            if c.shape != cr.shape:
                return float("inf")
            scale = max(1.0, float(np.max(np.abs(cr))) if cr.size else 1.0)
            worst = max(worst, float(np.max(np.abs(c - cr), initial=0.0)) / scale)
    return worst


def mse_gap(prog_models: Sequence[Dict], ref_models: Sequence, psi: float) -> float:
    """Widest difference of a generator's mean squared evaluation over its
    class's training rows (the quantity OAVI holds to ``psi``), over ``psi``.
    Only meaningful when the structures agree."""
    worst = 0.0
    for p, r in zip(prog_models, ref_models):
        for v, vr in zip(p["gen_mse"], r.gen_mse):
            worst = max(worst, abs(float(v) - float(vr)) / psi)
    return worst


def column_gap(F: np.ndarray, F_ref: np.ndarray) -> float:
    """Widest relative error of a column (a feature, or a class's SVM
    score): per column, the root mean square of its difference over the
    rows, over the root mean square of the reference's column; the largest
    over the columns.  (The largest single entry would be set by the
    program's own float32 coefficient error in directions the data hardly
    spans, which moves single rows but not a column; see PERF.md.)"""
    F = np.asarray(F, np.float64)
    F_ref = np.asarray(F_ref, np.float64)
    if F.shape != F_ref.shape:
        return float("inf")
    if F.size == 0:
        return 0.0
    err = np.sqrt(np.mean((F - F_ref) ** 2, axis=0))
    scale = np.maximum(np.sqrt(np.mean(F_ref ** 2, axis=0)), 1e-30)
    return float(np.max(err / scale))


def label_gap(labels: np.ndarray, ref_scores: np.ndarray, classes: np.ndarray) -> float:
    """Widest gap by which the reference's score of an answered label lies
    below the reference's best score for that row (0 where they agree; a
    label the reference does not know reads infinite)."""
    labels = np.asarray(labels)
    if labels.shape[0] != ref_scores.shape[0]:
        return float("inf")
    if labels.shape[0] == 0:
        return 0.0
    pos = np.searchsorted(classes, labels)
    pos = np.clip(pos, 0, len(classes) - 1)
    if np.any(classes[pos] != labels):
        return float("inf")
    chosen = ref_scores[np.arange(labels.shape[0]), pos]
    return float(np.max(ref_scores.max(axis=1) - chosen))


def judge(values: Dict[str, float], limits: Dict[str, Dict]) -> List[Dict]:
    """One line per number that the cell's limits name: its value, its
    limit, and whether it holds (``value <= limit``; NaN never holds, and a
    number the run did not produce reads infinite)."""
    out = []
    for name, spec in limits.items():
        limit = float(spec["limit"])
        v = float(values.get(name, float("inf")))
        out.append({"name": name, "value": v, "limit": limit,
                    "ok": bool(v <= limit)})
    return out
