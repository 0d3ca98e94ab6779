"""Pure-JAX SVMs for the paper's classification pipeline (Algorithm 2).

Two models, built from scratch (no scikit-learn in this container):

* :class:`LinearSVM` — l1-regularized squared-hinge linear SVM, one-vs-rest,
  trained with FISTA (accelerated proximal gradient; the l1 prox is
  soft-thresholding).  This is the paper's downstream classifier for the
  OAVI/ABM/VCA feature transforms ("l1-penalized squared hinge loss",
  Section 6.1).
* :class:`PolySVM` — polynomial-kernel SVM baseline with l2 regularization,
  one-vs-rest, trained in the (kernelized) primal with accelerated gradient
  descent on the dual coefficients.  Exact kernel up to ``max_kernel_samples``
  training points; beyond that a uniform subsample anchors the kernel
  expansion (documented in stats, mirrors the paper's iteration-capped
  LIBSVM behaviour on `skin`).

Both train loops are jitted ``lax.while_loop``s with fixed shapes.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Linear l1 squared-hinge SVM (FISTA)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinearSVMConfig:
    lam: float = 1e-4  # l1 penalty
    max_iter: int = 10_000
    tol: float = 1e-4
    dtype: str = "float32"


def _squared_hinge_grad(W, b, Xb, Y):
    """Mean squared-hinge loss + gradients.  Y in {-1, +1}, shape (m, k)."""
    m = Xb.shape[0]
    scores = jnp.matmul(Xb, W, precision=jax.lax.Precision.HIGHEST) + b  # (m, k)
    margin = 1.0 - Y * scores
    active = jnp.maximum(margin, 0.0)
    loss = jnp.mean(jnp.sum(active * active, axis=1))
    g_scores = (-2.0 / m) * (active * Y)  # (m, k)
    gW = jnp.matmul(Xb.T, g_scores, precision=jax.lax.Precision.HIGHEST)
    gb = jnp.sum(g_scores, axis=0)
    return loss, gW, gb


def _soft_threshold(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


@partial(jax.jit, static_argnames=("max_iter",))
def _fista(X, Y, lam, step, max_iter, tol):
    p, k = X.shape[1], Y.shape[1]
    dtype = X.dtype
    W = jnp.zeros((p, k), dtype)
    b = jnp.zeros((k,), dtype)

    def cond(state):
        W, b, Wz, bz, t, i, delta = state
        return jnp.logical_and(i < max_iter, delta > tol)

    def body(state):
        W, b, Wz, bz, t, i, _ = state
        _, gW, gb = _squared_hinge_grad(Wz, bz, X, Y)
        W_new = _soft_threshold(Wz - step * gW, step * lam)
        b_new = bz - step * gb  # bias unpenalized
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t_new
        Wz_new = W_new + beta * (W_new - W)
        bz_new = b_new + beta * (b_new - b)
        delta = jnp.max(jnp.abs(W_new - W)) + jnp.max(jnp.abs(b_new - b))
        return (W_new, b_new, Wz_new, bz_new, t_new, i + 1, delta)

    one = jnp.asarray(1.0, dtype)
    state = (W, b, W, b, one, jnp.asarray(0, jnp.int32), jnp.asarray(jnp.inf, dtype))
    W, b, *_, i, delta = jax.lax.while_loop(cond, body, state)
    return W, b, i


class LinearSVM:
    """One-vs-rest l1 squared-hinge linear SVM."""

    def __init__(self, config: LinearSVMConfig = LinearSVMConfig()):
        self.config = config
        self.W: Optional[np.ndarray] = None
        self.b: Optional[np.ndarray] = None
        self.classes_: Optional[np.ndarray] = None
        self.stats: Dict = {}

    def fit(self, X, y) -> "LinearSVM":
        dt = jnp.dtype(self.config.dtype)
        X = jnp.asarray(np.asarray(X), dt)
        y = np.asarray(y)
        self.classes_ = np.unique(y)
        Y = np.where(y[:, None] == self.classes_[None, :], 1.0, -1.0)
        Y = jnp.asarray(Y, dt)
        # Lipschitz constant of the squared-hinge gradient: 2/m * lmax(X~^T X~)
        m = X.shape[0]
        Xb = jnp.concatenate([X, jnp.ones((m, 1), dt)], axis=1)
        # power iteration for the top singular value
        v = jnp.ones((Xb.shape[1],), dt)
        hi = jax.lax.Precision.HIGHEST  # f32 matvecs, also on TPU

        def gram_v(v):
            return jnp.matmul(Xb.T, jnp.matmul(Xb, v, precision=hi), precision=hi)

        for _ in range(20):
            v = gram_v(v)
            v = v / jnp.maximum(jnp.linalg.norm(v), 1e-30)
        lmax = jnp.dot(v, gram_v(v), precision=hi)
        step = 1.0 / jnp.maximum(2.0 * lmax / m, 1e-12)
        W, b, iters = _fista(
            X, Y, jnp.asarray(self.config.lam, dt), step,
            self.config.max_iter, jnp.asarray(self.config.tol, dt),
        )
        self.W, self.b = np.asarray(W), np.asarray(b)
        self.stats = {"iters": int(iters), "nnz": int((np.abs(self.W) > 0).sum())}
        return self

    def decision_function(self, X) -> np.ndarray:
        return np.asarray(X) @ self.W + self.b

    def predict(self, X) -> np.ndarray:
        return self.classes_[np.argmax(self.decision_function(X), axis=1)]

    def score(self, X, y) -> float:
        return float(np.mean(self.predict(X) == np.asarray(y)))


# ---------------------------------------------------------------------------
# Polynomial-kernel SVM (l2, squared hinge, kernelized primal)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PolySVMConfig:
    degree: int = 3
    coef0: float = 1.0
    gamma: float = 1.0
    lam: float = 1e-3  # l2 penalty
    max_iter: int = 10_000
    tol: float = 1e-3
    max_kernel_samples: int = 4096
    dtype: str = "float32"
    seed: int = 0


def _poly_kernel(Xa, Xb, gamma, coef0, degree):
    return (gamma * (Xa @ Xb.T) + coef0) ** degree


@partial(jax.jit, static_argnames=("max_iter",))
def _kernel_agd(K, Y, lam, step, max_iter, tol):
    """Accelerated GD on f(alpha) = mean squared hinge(K alpha) + lam alpha^T K alpha.

    Stops on *relative* gradient norm (||g||_inf <= tol * ||g_0||_inf) so the
    criterion is scale-free w.r.t. kernel magnitude and step size.
    """
    r, k = K.shape[1], Y.shape[1]
    dtype = K.dtype
    m = Y.shape[0]
    A = jnp.zeros((r, k), dtype)

    def grad(Az):
        scores = K @ Az  # (m, k) — K here is the (m, r) cross-kernel
        margin = jnp.maximum(1.0 - Y * scores, 0.0)
        g_scores = (-2.0 / m) * (margin * Y)
        return K.T @ g_scores + 2.0 * lam * Az

    g0 = jnp.max(jnp.abs(grad(A)))

    def cond(state):
        A, Az, t, i, gnorm = state
        return jnp.logical_and(i < max_iter, gnorm > tol * g0)

    def body(state):
        A, Az, t, i, _ = state
        g = grad(Az)
        A_new = Az - step * g
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        Az_new = A_new + ((t - 1.0) / t_new) * (A_new - A)
        return (A_new, Az_new, t_new, i + 1, jnp.max(jnp.abs(g)))

    one = jnp.asarray(1.0, dtype)
    state = (A, A, one, jnp.asarray(0, jnp.int32), jnp.asarray(jnp.inf, dtype))
    A, _, _, i, _ = jax.lax.while_loop(cond, body, state)
    return A, i


class PolySVM:
    def __init__(self, config: PolySVMConfig = PolySVMConfig()):
        self.config = config
        self.anchors: Optional[np.ndarray] = None
        self.A: Optional[np.ndarray] = None
        self.classes_: Optional[np.ndarray] = None
        self.stats: Dict = {}

    def fit(self, X, y) -> "PolySVM":
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        X = np.asarray(X)
        y = np.asarray(y)
        m = X.shape[0]
        rng = np.random.default_rng(cfg.seed)
        if m > cfg.max_kernel_samples:
            idx = rng.choice(m, cfg.max_kernel_samples, replace=False)
            anchors = X[idx]
            self.stats["subsampled"] = True
        else:
            anchors = X
            self.stats["subsampled"] = False
        self.anchors = anchors
        self.classes_ = np.unique(y)
        Y = jnp.asarray(np.where(y[:, None] == self.classes_[None, :], 1.0, -1.0), dt)
        K = _poly_kernel(jnp.asarray(X, dt), jnp.asarray(anchors, dt),
                         cfg.gamma, cfg.coef0, cfg.degree)
        # step from the Lipschitz constant 2 lmax(K^T K)/m + 2 lam lmax(K)
        v = jnp.ones((K.shape[1],), dt)
        for _ in range(20):
            v = K.T @ (K @ v)
            v = v / jnp.maximum(jnp.linalg.norm(v), 1e-30)
        lmax = v @ (K.T @ (K @ v))
        L = 2.0 * lmax / m + 2.0 * cfg.lam * jnp.sqrt(lmax)
        step = 1.0 / jnp.maximum(L, 1e-12)
        A, iters = _kernel_agd(K, Y, jnp.asarray(cfg.lam, dt), step,
                               cfg.max_iter, jnp.asarray(cfg.tol, dt))
        self.A = np.asarray(A)
        self.stats["iters"] = int(iters)
        return self

    def decision_function(self, X) -> np.ndarray:
        cfg = self.config
        K = _poly_kernel(jnp.asarray(np.asarray(X), jnp.dtype(cfg.dtype)),
                         jnp.asarray(self.anchors, jnp.dtype(cfg.dtype)),
                         cfg.gamma, cfg.coef0, cfg.degree)
        return np.asarray(K @ self.A)

    def predict(self, X) -> np.ndarray:
        return self.classes_[np.argmax(self.decision_function(X), axis=1)]

    def score(self, X, y) -> float:
        return float(np.mean(self.predict(X) == np.asarray(y)))
