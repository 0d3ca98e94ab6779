"""ABM — the Approximate Buchberger–Möller algorithm (Limbeck 2013).

Baseline used by the paper (Section 6).  Same border machinery as OAVI, but
each border term is decided by an eigendecomposition of the *extended* Gram
matrix ``[[A^T A, A^T b], [b^T A, b^T b]] / m`` (the paper's modification:
"instead of applying the SVD to O(X) we apply the SVD to A^T A when faster"):
the smallest eigenvalue is the minimal MSE of any unit-coefficient polynomial
with terms in O ∪ {u}, and its eigenvector gives the coefficients.

A border term becomes a generator iff ``lambda_min <= psi``.  Coefficients are
rescaled so the leading-term coefficient is 1 (monic) for the feature
transform, mirroring OAVI's (psi, 1)-convention; the acceptance test itself is
on the unit-norm polynomial (which is exactly ABM's spurious-vanishing-prone
behaviour the paper discusses).
"""

from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import terms as terms_mod
from .oavi import Generator, OAVIModel, _append_columns
from .ordering import pearson_order


@dataclasses.dataclass(frozen=True)
class ABMConfig:
    psi: float = 0.005
    max_degree: int = 10
    cap_terms: int = 256
    cap_border: int = 64
    dtype: str = "float32"
    ordering: str = "pearson"


def _make_degree_step(cfg: ABMConfig, reduce_fn=None):
    rfn = reduce_fn if reduce_fn is not None else (lambda x: x)

    def degree_step(A, X, AtA, ell0, parents, vars_, valid, m_total):
        dtype = A.dtype
        Lcap = A.shape[1]
        K = parents.shape[0]
        psi = jnp.asarray(cfg.psi, dtype)
        inv_m = jnp.asarray(1.0 / m_total, dtype)

        P = jnp.take(A, parents, axis=1)
        B = P * jnp.take(X, vars_, axis=1)
        hi = jax.lax.Precision.HIGHEST  # f32 Grams, also on TPU
        QL = rfn(jnp.matmul(A.T, B, precision=hi)) * inv_m  # (L, K)
        C = rfn(jnp.matmul(B.T, B, precision=hi)) * inv_m  # (K, K)

        def body(a, carry):
            AtA_c, ell, accepted, slots, coeffs, lams = carry
            q = QL[:, a]
            appended_before = (jnp.arange(K) < a) & (~accepted) & (slots < Lcap) & valid
            safe = jnp.where(appended_before, slots, 0)
            q = q.at[safe].add(jnp.where(appended_before, C[:, a], 0.0), mode="drop")
            btb = C[a, a]

            onehot = (jnp.arange(Lcap) == ell).astype(dtype)
            mask = (jnp.arange(Lcap) < ell).astype(dtype)
            # extended Gram with the candidate placed at slot `ell`;
            # inactive block diag set to 2 so padded eigvals are never minimal
            M = (
                AtA_c
                + jnp.outer(onehot, q)
                + jnp.outer(q, onehot)
                + btb * jnp.outer(onehot, onehot)
            )
            keepm = mask + onehot
            Mmask = M * keepm[:, None] * keepm[None, :]
            Mpad = Mmask + 2.0 * jnp.diag(1.0 - keepm)
            evals, evecs = jnp.linalg.eigh(Mpad)
            lam = evals[0]
            v = evecs[:, 0] * keepm
            accept = (lam <= psi) & valid[a]

            # monic coefficients: divide by the entry at slot ell
            lead = v[jnp.argmax(onehot)]
            lead = jnp.where(jnp.abs(lead) > 1e-12, lead, 1e-12)
            monic = v / lead
            coef = monic * mask  # non-leading part

            def appended(args):
                AtA_i, ell_i, slots_i = args
                AtA_n = (
                    AtA_i
                    + jnp.outer(onehot, q)
                    + jnp.outer(q, onehot)
                    + btb * jnp.outer(onehot, onehot)
                )
                return AtA_n, ell_i + 1, slots_i.at[a].set(ell_i)

            AtA_c, ell, slots = jax.lax.cond(
                (~accept) & valid[a], appended, lambda x: x, (AtA_c, ell, slots)
            )
            accepted = accepted.at[a].set(accept)
            coeffs = coeffs.at[a].set(jnp.where(accept, coef, 0.0))
            lams = lams.at[a].set(lam)
            return AtA_c, ell, accepted, slots, coeffs, lams

        carry = (
            AtA,
            ell0,
            jnp.zeros((K,), bool),
            jnp.full((K,), Lcap, jnp.int32),
            jnp.zeros((K, Lcap), dtype),
            jnp.zeros((K,), dtype),
        )
        AtA, ell, accepted, slots, coeffs, lams = jax.lax.fori_loop(0, K, body, carry)
        appended = (~accepted) & valid & (slots < Lcap)
        A = _append_columns(A, B, slots, appended)
        return A, AtA, ell, accepted, slots, coeffs, lams

    return degree_step


def fit(X, config: ABMConfig = ABMConfig()) -> OAVIModel:
    t0 = time.perf_counter()
    dtype = jnp.dtype(config.dtype)
    X = np.asarray(X)
    m, n = X.shape

    perm = None
    if config.ordering in ("pearson", "reverse_pearson"):
        perm = pearson_order(X, reverse=(config.ordering == "reverse_pearson"))
        X = X[:, perm]

    Xd = jnp.asarray(X, dtype)
    book = terms_mod.TermBook(n=n)
    generators: List[Generator] = []

    Lcap = int(config.cap_terms)
    A = jnp.zeros((m, Lcap), dtype).at[:, 0].set(1.0)
    AtA = jnp.zeros((Lcap, Lcap), dtype).at[0, 0].set(1.0)
    ell = 1

    degree_step = jax.jit(_make_degree_step(config))
    stats = {"border_sizes": [], "degrees": [], "m": m, "n": n}

    d = 0
    while True:
        d += 1
        if d > config.max_degree:
            stats["termination"] = "max_degree"
            break
        border = book.border(d)
        if not border:
            stats["termination"] = "empty_border"
            break
        K = len(border)
        stats["border_sizes"].append(K)
        stats["degrees"].append(d)
        if ell + K > Lcap:
            raise RuntimeError("ABM capacity exhausted; raise cap_terms")

        Kcap = max(config.cap_border, 1 << (K - 1).bit_length())
        parents = np.zeros((Kcap,), np.int32)
        vars_ = np.zeros((Kcap,), np.int32)
        valid = np.zeros((Kcap,), bool)
        for i, (term, parent, j) in enumerate(border):
            parents[i] = book.index[parent]
            vars_[i] = j
            valid[i] = True

        A, AtA, _, accepted, slots, coeffs, lams = degree_step(
            A, Xd, AtA, jnp.asarray(ell, jnp.int32), jnp.asarray(parents),
            jnp.asarray(vars_), jnp.asarray(valid), float(m),
        )
        accepted = np.asarray(accepted)
        coeffs = np.asarray(coeffs)
        lams = np.asarray(lams)

        for i, (term, parent, j) in enumerate(border):
            if accepted[i]:
                generators.append(
                    Generator(
                        term=term,
                        parent_idx=book.index[parent],
                        var=j,
                        coeffs=coeffs[i, : len(book)].copy(),
                        mse=float(lams[i]),
                    )
                )
            else:
                book.append(term, parent, j)
        ell = len(book)

    stats["time_total"] = time.perf_counter() - t0
    stats["num_G"] = len(generators)
    stats["num_O"] = len(book)
    stats["G_plus_O"] = len(generators) + len(book)
    return OAVIModel(
        n=n, psi=config.psi, book=book, generators=generators,
        feature_perm=perm, stats=stats, dtype=config.dtype,
    )
