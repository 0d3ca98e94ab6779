"""Plain reference of the paper's Algorithm 2, independent of the program.

Written from the paper (Wirth, Kera, Pokutta, ICLR 2023): min-max scaling,
per-class Pearson feature ordering (Algorithm 5), OAVI (Algorithm 1) over
DegLex borders with the CG oracle on the l1 ball of radius ``tau - 1``,
warm-started by the closed-form optimum (IHB, Sec. 4.4) and switched off for
good the first time that warm start leaves the ball (Sec. 4.4.3), then the
feature transform ``x -> |g(x)|`` and an l1-penalised squared-hinge linear
SVM (one-vs-rest, FISTA).  It imports nothing of the program under test.

Everything but the SVM runs in numpy on the host, straightforwardly: Gram
matrices straight from the evaluation matrix, the closed form by a direct
inverse.  The SVM runs in jnp float32 on the device (it iterates over every
training row thousands of times).

``precision`` names the arithmetic: ``"highest"`` is the reference itself,
float64 on the host and float32 at ``Precision.HIGHEST`` in the SVM, at
least as precise as the float32 the configuration states; ``"high"`` is the
control that a comparison has to reject, every matrix product in three bf16
passes with float32 accumulation (what ``Precision.HIGH`` does on a TPU),
computed explicitly from bf16 pieces so that it reads the same on any
backend.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Dict, List, Tuple

import numpy as np

F32 = np.float32
PRECISIONS = ("highest", "high")


def host_dtype(precision: str):
    return np.float64 if precision == "highest" else F32


# ---------------------------------------------------------------------------
# Matrix products at a stated precision
# ---------------------------------------------------------------------------


def _bf16_pieces(a: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    import ml_dtypes

    hi = a.astype(ml_dtypes.bfloat16).astype(F32)
    lo = (a - hi).astype(ml_dtypes.bfloat16).astype(F32)
    return hi, lo


def mm(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """``a @ b``: in float64 at ``"highest"``; at ``"high"`` three bf16
    products of four, accumulated in float32."""
    if precision == "highest":
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    a = np.asarray(a, F32)
    b = np.asarray(b, F32)
    ah, al = _bf16_pieces(a)
    bh, bl = _bf16_pieces(b)
    return ah @ bh + (ah @ bl + al @ bh)


def _mm_jnp(a, b, precision: str):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hi)
    ah = a.astype(jnp.bfloat16).astype(jnp.float32)
    al = (a - ah).astype(jnp.bfloat16).astype(jnp.float32)
    bh = b.astype(jnp.bfloat16).astype(jnp.float32)
    bl = (b - bh).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(ah, bh, precision=hi) + (
        jnp.matmul(ah, bl, precision=hi) + jnp.matmul(al, bh, precision=hi)
    )


# ---------------------------------------------------------------------------
# Pre-processing: min-max scaling and Pearson ordering
# ---------------------------------------------------------------------------


def minmax_fit(X: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    X = np.asarray(X, np.float64)
    lo = X.min(axis=0)
    rng = X.max(axis=0) - lo
    return lo, np.where(rng > 0, 1.0 / np.maximum(rng, 1e-300), 0.0)


def minmax_apply(X: np.ndarray, lo: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """Rows scaled into [0, 1]^n, as the float32 the models are fitted on."""
    return np.clip((np.asarray(X, np.float64) - lo) * scale, 0.0, 1.0).astype(F32)


def pearson_order(X: np.ndarray) -> np.ndarray:
    """Features sorted increasingly by the sum of their absolute Pearson
    correlations with all features (Algorithm 5); ties keep input order."""
    X = np.asarray(X, np.float64)
    Xc = X - X.mean(axis=0, keepdims=True)
    std = np.sqrt((Xc * Xc).sum(axis=0))
    denom = np.outer(std, std)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(denom > 0, (Xc.T @ Xc) / np.maximum(denom, 1e-300), 0.0)
    np.fill_diagonal(r, 1.0)
    return np.argsort(np.abs(r).sum(axis=1), kind="stable")


# ---------------------------------------------------------------------------
# OAVI (Algorithm 1) with the CG oracle and IHB
# ---------------------------------------------------------------------------

Term = Tuple[int, ...]


def _deglex_key(t: Term):
    return (sum(t), tuple(-e for e in t))


def border(O_deg: Dict[int, List[Term]], d: int, n: int) -> List[Tuple[Term, Term, int]]:
    """Degree-``d`` border of the order ideal ``O``, DegLex-sorted, as
    ``(term, parent, var)`` with ``term = parent * x_var``: the first parent
    of ``O_{d-1}`` (in DegLex order) and the smallest variable that give it."""
    prev = O_deg.get(d - 1, [])
    prev_set = set(prev)
    found: Dict[Term, Tuple[Term, int]] = {}
    for parent in prev:
        for j in range(n):
            t = parent[:j] + (parent[j] + 1,) + parent[j + 1:]
            if t not in found:
                found[t] = (parent, j)
    out = []
    for t, (parent, j) in found.items():
        divisors = [t[:i] + (t[i] - 1,) + t[i + 1:] for i in range(n) if t[i] > 0]
        if all(dv in prev_set for dv in divisors):
            out.append((t, parent, j))
    out.sort(key=lambda x: _deglex_key(x[0]))
    return out


@dataclasses.dataclass
class ClassModel:
    perm: np.ndarray  # feature order (Pearson)
    terms: List[Term]  # O, in the order appended (index 0: the constant)
    parents: List[int]  # index of each term's parent in ``terms`` (-1: constant)
    vars: List[int]
    gen_terms: List[Term]  # leading term of each generator
    gen_parent: List[int]
    gen_var: List[int]
    gen_coeffs: List[np.ndarray]  # coefficients over O at accept time
    gen_mse: List[float]  # mean squared evaluation over the class's rows


def _cg(Q, q, btb, y0, r, psi, eps, max_iter, precision):
    """Frank-Wolfe with exact line search on f(y) = y'Qy + 2q'y + btb over
    the l1 ball of radius r, from y0.  Stops when the gap of the previous
    iterate is at most eps, when f <= psi (a generator is found), when
    f - gap > psi (none can exist), or after max_iter steps (Sec. 6.1).
    Returns (y, f)."""

    dt = Q.dtype.type

    def f_of(y):
        return dt(np.sum(y * mm(Q, y, precision)) + dt(2.0) * np.sum(q * y) + btb)

    def grad_gap(y):
        g = (dt(2.0) * (mm(Q, y, precision) + q)).astype(dt)
        i = int(np.argmax(np.abs(g)))
        w = np.zeros_like(y)
        w[i] = (-np.sign(g[i]) if g[i] != 0 else dt(1.0)) * r
        return g, w, dt(np.sum(g * (y - w)))

    y = y0.astype(dt)
    f = f_of(y)
    _, _, gap = grad_gap(y)
    k = 0
    while k < max_iter and gap > eps and f > psi and f - gap <= psi:
        g, w, gap = grad_gap(y)
        d = w - y
        dQd = dt(np.sum(d * mm(Q, d, precision)))
        gamma = -dt(np.sum(g * d)) / max(dt(2.0) * dQd, dt(1e-30)) if dQd > 0 else dt(1.0)
        y = (y + dt(min(max(gamma, 0.0), 1.0)) * d).astype(dt)
        f = f_of(y)
        k += 1
    return y, f


def fit_class(Xc: np.ndarray, method: Dict, precision: str) -> ClassModel:
    """OAVI (Algorithm 1) on one class's scaled rows."""
    dt = host_dtype(precision)
    psi = dt(method["psi"])
    r = dt(method["tau"] - 1.0)
    eps = dt(method["eps_frac"] * method["psi"])
    max_iter = int(method["max_solver_iter"])
    m, n = Xc.shape
    perm = pearson_order(Xc)
    X = np.ascontiguousarray(Xc[:, perm], dt)
    one: Term = (0,) * n
    terms, parents, vars_ = [one], [-1], [-1]
    index = {one: 0}
    O_deg: Dict[int, List[Term]] = {0: [one]}
    cols = [np.ones((m,), dt)]
    model = ClassModel(perm, terms, parents, vars_, [], [], [], [], [])
    inv_m = dt(1.0 / m)
    ihb_live = True
    for d in range(1, int(method["max_degree"]) + 1):
        cands = border(O_deg, d, n)
        if not cands:
            break
        A = np.stack(cols, axis=1)
        Q = mm(A.T, A, precision) * inv_m
        N = np.linalg.inv(Q)
        for t, parent, j in cands:
            b = cols[index[parent]] * X[:, j]
            q = mm(A.T, b[:, None], precision)[:, 0] * inv_m
            btb = mm(b[None, :], b[:, None], precision)[0, 0] * inv_m
            y0 = -mm(N, q[:, None], precision)[:, 0]
            warm = np.zeros_like(y0)
            if ihb_live:
                if np.sum(np.abs(y0)) <= r:
                    warm = y0
                else:
                    ihb_live = False
            y, f = _cg(Q, q, btb, warm, r, psi, eps, max_iter, precision)
            if f <= psi:
                model.gen_terms.append(t)
                model.gen_parent.append(index[parent])
                model.gen_var.append(j)
                model.gen_coeffs.append(y.copy())
                model.gen_mse.append(float(f))
                continue
            index[t] = len(terms)
            terms.append(t)
            parents.append(index[parent])
            vars_.append(j)
            O_deg.setdefault(d, []).append(t)
            cols.append(b)
            A = np.stack(cols, axis=1)
            Q = mm(A.T, A, precision) * inv_m
            N = np.linalg.inv(Q)
    return model


def class_features(model: ClassModel, Z: np.ndarray, precision: str) -> np.ndarray:
    """``|g(Z)|`` for every generator of one class model: (q, |G|)."""
    dt = host_dtype(precision)
    Zp = np.asarray(Z, dt)[:, model.perm]
    cols = [np.ones((Zp.shape[0],), dt)]
    for i in range(1, len(model.terms)):
        cols.append(cols[model.parents[i]] * Zp[:, model.vars[i]])
    O = np.stack(cols, axis=1)
    k = len(model.gen_terms)
    if k == 0:
        return np.zeros((Zp.shape[0], 0), dt)
    C = np.zeros((O.shape[1], k), dt)
    for g, c in enumerate(model.gen_coeffs):
        C[: len(c), g] = c
    lead = O[:, model.gen_parent] * Zp[:, model.gen_var]
    return np.abs(mm(O, C, precision) + lead)


def generator_mse(model: ClassModel, Zc: np.ndarray, coeffs=None) -> np.ndarray:
    """Each generator's mean squared evaluation over the rows ``Zc``, in
    float64, with the model's coefficients or with ``coeffs`` in their
    place (the same terms)."""
    if coeffs is not None:
        model = dataclasses.replace(model, gen_coeffs=[np.asarray(c, np.float64) for c in coeffs])
    return np.mean(class_features(model, Zc, "highest") ** 2, axis=0)


# ---------------------------------------------------------------------------
# l1 squared-hinge linear SVM (FISTA), on the device
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _svm_programs(precision: str, max_iter: int, power_iters: int):
    import jax
    import jax.numpy as jnp

    mmp = partial(_mm_jnp, precision=precision)

    @jax.jit
    def step_size(Xb):
        def gram_v(v):
            return mmp(Xb.T, mmp(Xb, v[:, None]))[:, 0]

        def body(_, v):
            v = gram_v(v)
            return v / jnp.maximum(jnp.linalg.norm(v), 1e-30)

        v = jax.lax.fori_loop(0, power_iters, body, jnp.ones((Xb.shape[1],), Xb.dtype))
        lmax = jnp.sum(v * gram_v(v))
        return 1.0 / jnp.maximum(2.0 * lmax / Xb.shape[0], 1e-12)

    @jax.jit
    def fista(X, Y, lam, step, tol):
        m = X.shape[0]

        def soft(x, t):
            return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)

        def cond(s):
            return jnp.logical_and(s[5] < max_iter, s[6] > tol)

        def body(s):
            W, b, Wz, bz, t, i, _ = s
            scores = mmp(X, Wz) + bz
            active = jnp.maximum(1.0 - Y * scores, 0.0)
            g = (-2.0 / m) * (active * Y)
            gW = mmp(X.T, g)
            gb = jnp.sum(g, axis=0)
            W1 = soft(Wz - step * gW, step * lam)
            b1 = bz - step * gb
            t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            beta = (t - 1.0) / t1
            delta = jnp.max(jnp.abs(W1 - W)) + jnp.max(jnp.abs(b1 - b))
            return (W1, b1, W1 + beta * (W1 - W), b1 + beta * (b1 - b), t1, i + 1, delta)

        p, k = X.shape[1], Y.shape[1]
        W = jnp.zeros((p, k), X.dtype)
        b = jnp.zeros((k,), X.dtype)
        s0 = (W, b, W, b, jnp.asarray(1.0, X.dtype), jnp.asarray(0, jnp.int32),
              jnp.asarray(jnp.inf, X.dtype))
        W, b, _, _, _, i, _ = jax.lax.while_loop(cond, body, s0)
        return W, b, i

    return step_size, fista


def svm_fit(F: np.ndarray, y: np.ndarray, classes: np.ndarray, svm: Dict, precision: str):
    """One-vs-rest l1 squared-hinge SVM on features ``F``.  Returns (W, b)."""
    import jax.numpy as jnp

    step_size, fista = _svm_programs(precision, int(svm["max_iter"]), int(svm["power_iters"]))
    X = jnp.asarray(F, jnp.float32)
    Y = jnp.asarray(np.where(y[:, None] == classes[None, :], 1.0, -1.0), jnp.float32)
    Xb = jnp.concatenate([X, jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)
    step = step_size(Xb)
    W, b, _ = fista(X, Y, jnp.float32(svm["lam"]), step, jnp.float32(svm["tol"]))
    return np.asarray(W), np.asarray(b)


# ---------------------------------------------------------------------------
# Algorithm 2
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Reference:
    lo: np.ndarray
    scale: np.ndarray
    classes: np.ndarray
    models: List[ClassModel]
    W: np.ndarray
    b: np.ndarray
    precision: str
    psi: float
    lam: float

    def features_scaled(self, Z: np.ndarray) -> np.ndarray:
        return np.concatenate(
            [class_features(m, Z, self.precision) for m in self.models], axis=1
        )

    def scores_scaled(self, Z: np.ndarray) -> np.ndarray:
        return mm(self.features_scaled(Z), self.W, self.precision) + self.b

    def predict_scaled(self, Z: np.ndarray) -> np.ndarray:
        return self.classes[np.argmax(self.scores_scaled(Z), axis=1)]


def fit(X_raw: np.ndarray, y: np.ndarray, method: Dict, svm: Dict,
        precision: str = "highest") -> Reference:
    """Algorithm 2 on raw training rows: scale, one OAVI model per class (in
    increasing label order), transform, SVM."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    lo, scale = minmax_fit(X_raw)
    Z = minmax_apply(X_raw, lo, scale)
    y = np.asarray(y)
    classes = np.unique(y)
    models = [fit_class(Z[y == c], method, precision) for c in classes]
    ref = Reference(lo, scale, classes, models, None, None, precision, float(method["psi"]),
                    float(svm["lam"]))
    F = ref.features_scaled(Z)
    ref.W, ref.b = svm_fit(F, y, classes, svm, precision)
    return ref
