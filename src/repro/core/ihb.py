"""Inverse Hessian Boosting (IHB) — Section 4.4 / Theorem 4.9.

OAVI solves a sequence of least-squares problems ``min_y ||A y + b||^2`` in
which ``A = O(X)`` grows by one column whenever a border term is appended to
``O``.  IHB maintains ``N = (A^T A)^{-1}`` across appends with the block
inverse update of Theorem 4.9 in ``O(l^2)`` elementary operations, so the
closed-form optimum ``y* = -N A^T b`` is available essentially for free and
serves as a (usually eps-accurate) warm start for the convex oracle.

All state is fixed-capacity: ``N`` is ``(L, L)`` with the *inactive* block set
to the identity (so the padded ``N`` is the exact inverse of the padded
``A^T A + I_inactive``), which keeps every update a dense masked operation
that jits once.

The state is *slimmed to the configured engine*: each of the three factors
(``AtA`` for the convex oracles, ``N`` for the Theorem 4.9 inverse, ``R``
for the beyond-paper Cholesky engine) is materialized and updated per
candidate only when the caller needs it — :func:`factors_for` maps an OAVI
configuration to its minimal factor set, and :func:`append_column` skips the
``None`` factors.  The paper-faithful full state (all three) remains the
default for direct users of this module.

The ``N`` update is dispatched through :func:`repro.kernels.ops.ihb_update`
(the Pallas kernel on TPU, its jnp reference elsewhere, or whichever the
caller's ``OAVIConfig.kernel`` names).

Beyond the paper, the Cholesky-factor engine (maintain the upper-triangular
``R`` with ``A^T A = R^T R``; appends are triangular solves) has conditioning
``kappa(A)`` instead of ``kappa(A)^2`` — recorded as a beyond-paper
optimization in EXPERIMENTS.md.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..kernels import ops as kernel_ops

# Matvecs at full f32 precision: TPU's default rounds f32 operands to bf16.
_HIGHEST = jax.lax.Precision.HIGHEST


class IHBState(NamedTuple):
    """Per-factor state; a factor the engine does not need is ``None``
    (``None`` is an empty pytree node, so slim and full states both jit)."""

    AtA: Optional[jax.Array]  # (L, L) Gram of active columns (zeros elsewhere)
    N: Optional[jax.Array]  # (L, L) inverse of (AtA_active ⊕ I_inactive)
    R: Optional[jax.Array]  # (L, L) upper-triangular Cholesky factor (ditto)


FACTORS_ALL: Tuple[str, ...] = ("ata", "n", "r")


def factors_for(
    engine: str,
    inverse_engine: str = "inverse",
    warm: bool = True,
    wihb: bool = False,
):
    """Minimal factor set for an OAVI configuration.

    * ``AtA`` — the Gram matrix itself is only needed as a solver Hessian:
      by the convex oracles (``engine='oracle'``) and by the WIHB sparse
      re-solve (``wihb``, which runs BPCG regardless of engine).
    * ``N`` / ``R`` — one of them backs the closed-form optimum: always for
      ``engine='fast'``, and for the oracle engine only when IHB warm starts
      are on (``warm``).
    """
    need = []
    if engine == "oracle" or wihb:
        need.append("ata")
    if engine == "fast" or warm:
        need.append("r" if inverse_engine == "chol" else "n")
    return tuple(need)


def init_state(Lcap: int, diag0: jax.Array, dtype=jnp.float32,
               factors: Tuple[str, ...] = FACTORS_ALL) -> IHBState:
    """State after the constant-1 column: AtA[0,0] = ||1||^2 = m."""
    eye = jnp.eye(Lcap, dtype=dtype)
    AtA = (
        jnp.zeros((Lcap, Lcap), dtype).at[0, 0].set(diag0)
        if "ata" in factors else None
    )
    N = eye.at[0, 0].set(1.0 / diag0) if "n" in factors else None
    R = eye.at[0, 0].set(jnp.sqrt(diag0)) if "r" in factors else None
    return IHBState(AtA=AtA, N=N, R=R)


def grow_state(state: IHBState, new_L: int) -> IHBState:
    """Double capacity device-side: each present factor is embedded into its
    padded identity/zero block with one ``dynamic_update_slice`` — no host
    numpy round-trip, so regrowth costs O(L^2) device work only.

    Factors may carry leading batch axes (the class-batched fit keeps one
    state per class, ``(k, L, L)``); only the trailing two axes grow.
    """

    def embed(M, identity: bool):
        if M is None:
            return None
        batch = M.shape[:-2]
        base = (
            jnp.eye(new_L, dtype=M.dtype)
            if identity else jnp.zeros((new_L, new_L), M.dtype)
        )
        base = jnp.broadcast_to(base, batch + (new_L, new_L))
        return jax.lax.dynamic_update_slice(base, M, (0,) * M.ndim)

    return IHBState(
        AtA=embed(state.AtA, identity=False),
        N=embed(state.N, identity=True),
        R=embed(state.R, identity=True),
    )


def batch_state(state: IHBState, k: int) -> IHBState:
    """Stack ``k`` copies of a (fresh) state along a new leading class axis —
    the batched initial state of the class-batched fit.  In the normalized
    Gram convention every class starts from the identical state
    (``AtA[0, 0] = 1``), so a broadcast-copy is exact."""
    rep = lambda M: None if M is None else jnp.repeat(M[None], k, axis=0)  # noqa: E731
    return IHBState(AtA=rep(state.AtA), N=rep(state.N), R=rep(state.R))


def closed_form_inverse(state: IHBState, q: jax.Array) -> jax.Array:
    """``y* = -N q`` (paper's IHB warm start).  ``q = A^T b`` padded."""
    return -inverse_times(state, q)


def inverse_times(state: IHBState, q: jax.Array) -> jax.Array:
    """``u = N q``: the warm start's matvec, which the Theorem 4.9 update
    (:func:`append_column`) reuses."""
    return jnp.matmul(state.N, q, precision=_HIGHEST)


def closed_form_cholesky(state: IHBState, q: jax.Array) -> jax.Array:
    """``y* = -(R^T R)^{-1} q`` via two triangular solves (beyond-paper)."""
    z = solve_triangular(state.R, q, trans=1, lower=False)
    return -solve_triangular(state.R, z, trans=0, lower=False)


def mse_from_solution(q: jax.Array, btb: jax.Array, y: jax.Array, m) -> jax.Array:
    """MSE(g, X) = (btb + q^T y) / m at the closed-form optimum y = -N q.

    (||A y + b||^2 = y^T AtA y + 2 q^T y + btb = -q^T y - ... collapses to
    btb + q^T y when y is the exact minimizer.)

    The inner product reduces via ``sum(q * y)``, the vmap-bit-stable form
    every in-algorithm MSE reduction uses (a fused dot lowers differently
    batched vs per-instance, breaking the class-batched path's bit-exactness
    — see :func:`repro.kernels.ref.ihb_update_ref`).
    """
    return (btb + jnp.sum(q * y)) / m


def append_column(
    state: IHBState,
    q: jax.Array,  # (L,) A^T b for the new column b (zeros at inactive idx)
    btb: jax.Array,  # ||b||^2
    ell: jax.Array,  # current active count == index where b lands
    *,
    u: Optional[jax.Array] = None,  # N q, when the caller already formed it
    kernel_kw: Optional[dict] = None,  # ops.ihb_update dispatch (OAVIConfig.kernel)
) -> IHBState:
    """Theorem 4.9 block inverse update + Cholesky append, both O(l^2).

    Only the factors present in ``state`` are updated (``None`` stays
    ``None``) — the per-candidate cost tracks the configured engine instead
    of always paying for all three factors.
    """
    dtype = q.dtype
    Lcap = q.shape[0]
    onehot = (jnp.arange(Lcap) == ell).astype(dtype)
    keep = 1.0 - onehot

    AtA = N = R = None

    if state.AtA is not None:
        # ---- AtA update: add row/col ell = (q, btb)
        AtA = (
            state.AtA
            + jnp.outer(onehot, q)
            + jnp.outer(q, onehot)
            + btb * jnp.outer(onehot, onehot)
        )

    if state.N is not None:
        # ---- inverse update (Thm 4.9): the Pallas kernel or its jnp
        # reference, as ``kernel_kw`` (or the backend) selects.
        N = kernel_ops.ihb_update(state.N, q, btb, ell, u=u, **(kernel_kw or {}))

    if state.R is not None:
        # ---- Cholesky append: R^T r = q ; rho = sqrt(btb - r^T r)
        r = solve_triangular(state.R, q, trans=1, lower=False)
        r = r * keep  # the inactive identity block must not leak into r
        rho2 = jnp.maximum(
            btb - jnp.dot(r, r, precision=_HIGHEST), jnp.asarray(1e-30, dtype)
        )
        rho = jnp.sqrt(rho2)
        col = r + rho * onehot
        # overwrite column ell of R (previously e_ell from the identity padding)
        R = state.R * (1.0 - onehot)[None, :] + jnp.outer(col, onehot)

    return IHBState(AtA=AtA, N=N, R=R)


def schur_complement(state: IHBState, q: jax.Array, btb: jax.Array) -> jax.Array:
    """``s = ||b||^2 - q^T N q`` — the (INF)/singularity guard of §4.4.3:
    if s <= 0 the new column is (numerically) dependent and IHB must stop."""
    return btb - jnp.dot(q, inverse_times(state, q), precision=_HIGHEST)
