"""Convex optimization oracles for OAVI (Line 7 / (CCOP)).

All solvers minimize the quadratic

    f(y) = (y^T Q y + 2 q^T y + btb) / m,      Q = A^T A,  q = A^T b,

either unconstrained (AGD) or over the l1-ball of radius ``r = tau - 1``
(CG / PCG / BPCG), exactly as in Sections 3.3 and 4.3 of the paper.  Working
in Gram form makes the per-iteration cost O(l^2) instead of O(m l); the
O(m l) part (computing Q, q incrementally) is done once per candidate term in
:mod:`repro.core.oavi` ("In BPCG, we first compute A^T A and A^T b").

Everything is fixed-capacity (padded to ``L`` columns with a boolean mask) so
the solvers jit once and are reused across OAVI's whole execution.

Early-termination rules follow Section 6.1 of the paper:
  * accuracy ``eps = eps_frac * psi`` (via the FW gap for CG variants, via the
    gradient norm for AGD),
  * stop when a vanishing coefficient vector has been constructed
    (``f <= psi``),
  * stop when no vanishing vector can exist (``f - gap > psi`` certifies
    ``f* > psi`` for CG variants),
  * hard iteration cap.

Each solver is one ``while_loop`` over its early-termination predicate
(``_run_while``): it stops the moment a certificate fires.  The same loop
rides the class-batched / streaming paths under ``vmap``: the batched loop
runs until its last lane stops, and a stopped lane carries its state as a
bitwise no-op (the same trick ``class_batch`` uses for finished classes),
so a lane's result does not depend on how long the others run, and a batch
pays for its slowest lane.  Each solver's ``cond``/``body``/``finish`` closures come
from its ``_*_parts`` helper.

All vector reductions use :func:`vdot` (elementwise multiply + sum) instead of
fused ``a @ b`` dots: the fused form lowers to a different reduction order
under ``vmap``, which would break the bit-identity contract between a batched
solve and its single-lane twin.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_INF = -jnp.inf


@dataclasses.dataclass(frozen=True)
class OracleConfig:
    name: str = "bpcg"  # 'agd' | 'cg' | 'pcg' | 'bpcg'
    tau: float = 1000.0  # l1 radius is tau - 1 (CCOP); ignored by AGD
    max_iter: int = 10_000
    eps_frac: float = 0.01  # solver accuracy = eps_frac * psi
    # AGD: number of power iterations used to estimate the smoothness constant
    power_iters: int = 30


class SolveResult(NamedTuple):
    y: jax.Array  # (L,) solution (padded with zeros outside the mask)
    f: jax.Array  # objective value (MSE of the candidate polynomial)
    gap: jax.Array  # FW gap (CG variants) or squared grad norm (AGD)
    iters: jax.Array  # iterations used


def _mv(Q, y):
    """Gram matvec at full f32 precision (TPU's default matmul precision
    rounds f32 operands to bf16, enough to move a solve across psi)."""
    return jnp.matmul(Q, y, precision=jax.lax.Precision.HIGHEST)


def vdot(a, b):
    """Vector dot as elementwise multiply + reduce — the vmap-bit-stable
    lowering (a fused ``a @ b`` reduces in a different order when batched;
    cf. ``repro.core.ihb.mse_from_solution``)."""
    return jnp.sum(a * b)


def quad_f(Q, q, btb, inv_m, y):
    return (vdot(y, _mv(Q, y)) + 2.0 * vdot(q, y) + btb) * inv_m


def quad_grad(Q, q, inv_m, y):
    return 2.0 * inv_m * (_mv(Q, y) + q)


def _line_search_quad(Q, inv_m, grad, d, gamma_max):
    """Exact line search for the quadratic along ``d``; clipped to
    ``[0, gamma_max]``.  f(y + g d) - f(y) = g <grad, d> + g^2 d^T Q d / m."""
    dQd = vdot(d, _mv(Q, d)) * inv_m
    num = -vdot(grad, d)
    gamma = jnp.where(dQd > 0, num / jnp.maximum(2.0 * dQd, 1e-30), gamma_max)
    return jnp.clip(gamma, 0.0, gamma_max)


# --------------------------------------------------------------------------
# Shared runner
# --------------------------------------------------------------------------


def _run_while(state0, cond, body, finish) -> "SolveResult":
    final = jax.lax.while_loop(cond, body, state0)
    return finish(final)


# --------------------------------------------------------------------------
# AGD (Nesterov) — unconstrained
# --------------------------------------------------------------------------


def _estimate_lmax(Q, mask, iters: int):
    """Power iteration on the masked Gram matrix."""
    v0 = jnp.where(mask, 1.0, 0.0).astype(Q.dtype)
    v0 = v0 / jnp.maximum(jnp.sqrt(vdot(v0, v0)), 1e-30)

    def body(_, v):
        w = _mv(Q, v)
        nrm = jnp.sqrt(vdot(w, w))
        return jnp.where(nrm > 0, w / jnp.maximum(nrm, 1e-30), v)

    v = jax.lax.fori_loop(0, iters, body, v0)
    return jnp.maximum(vdot(v, _mv(Q, v)), 1e-30)


def _agd_parts(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0):
    dtype = Q.dtype
    Lcap = Q.shape[0]
    inv_m = (1.0 / m).astype(dtype)
    maskf = mask.astype(dtype)
    if y0 is None:
        y0 = jnp.zeros((Lcap,), dtype)
    y0 = y0 * maskf
    lmax = _estimate_lmax(Q, mask, cfg.power_iters)
    step = 1.0 / (2.0 * lmax * inv_m)  # 1/L_smooth with L = 2 lmax / m
    eps = cfg.eps_frac * psi

    def cond(state):
        _, _, _, k, gnorm2 = state
        return jnp.logical_and(k < cfg.max_iter, gnorm2 > eps * eps)

    def body(state):
        y, z, t, k, _ = state
        g = quad_grad(Q, q, inv_m, z) * maskf
        y_new = z - step * g
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = y_new + ((t - 1.0) / t_new) * (y_new - y)
        return (y_new, z_new * maskf, t_new, k + 1, vdot(g, g))

    def finish(state):
        y, _, _, k, gnorm2 = state
        f = quad_f(Q, q, btb, inv_m, y)
        return SolveResult(y=y, f=f, gap=gnorm2, iters=k)

    g0 = quad_grad(Q, q, inv_m, y0) * maskf
    state0 = (y0, y0, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32), vdot(g0, g0))
    return state0, cond, body, finish


@partial(jax.jit, static_argnames=("cfg",))
def solve_agd(
    Q: jax.Array,
    q: jax.Array,
    btb: jax.Array,
    m: jax.Array,
    mask: jax.Array,
    psi: jax.Array,
    cfg: OracleConfig,
    y0: Optional[jax.Array] = None,
) -> SolveResult:
    return _run_while(*_agd_parts(Q, q, btb, m, mask, psi, cfg, y0))


# --------------------------------------------------------------------------
# Frank-Wolfe variants on the l1-ball of radius r = tau - 1
# --------------------------------------------------------------------------


def _fw_vertex(grad, mask, r):
    """Global LMO over the l1 ball: vertex -r*sign(grad_i*) e_{i*}."""
    score = jnp.where(mask, jnp.abs(grad), NEG_INF)
    i = jnp.argmax(score)
    s = -jnp.sign(grad[i])
    s = jnp.where(s == 0, 1.0, s)
    return i, s * r  # index, signed coordinate value


def _weights_to_point(wp, wm, r):
    return r * (wp - wm)


def _decompose_point(y, r, mask):
    """Represent y (||y||_1 <= r) as convex weights on vertices +/- r e_i.

    Leftover mass (1 - ||y||_1 / r) is split evenly between +r e_0 and -r e_0
    so it contributes 0 to the reconstructed point.
    """
    maskf = mask.astype(y.dtype)
    wp = jnp.maximum(y, 0.0) / r * maskf
    wm = jnp.maximum(-y, 0.0) / r * maskf
    leftover = jnp.maximum(1.0 - jnp.sum(wp + wm), 0.0)
    wp = wp.at[0].add(0.5 * leftover)
    wm = wm.at[0].add(0.5 * leftover)
    return wp, wm


class _FWState(NamedTuple):
    y: jax.Array
    wp: jax.Array  # weights on +r e_i
    wm: jax.Array  # weights on -r e_i
    f: jax.Array
    gap: jax.Array
    k: jax.Array


def _fw_cond(cfg, psi, state: _FWState):
    eps = cfg.eps_frac * psi
    not_converged = state.gap > eps
    not_vanishing = state.f > psi  # generator already found -> stop
    feasible_possible = (state.f - state.gap) <= psi  # lower bound on f*
    return jnp.logical_and(
        state.k < cfg.max_iter,
        jnp.logical_and(not_converged, jnp.logical_and(not_vanishing, feasible_possible)),
    )


def _fw_state0(Q, q, btb, inv_m, y0, wp0, wm0, mask, r):
    """Entry state carrying the TRUE FW gap at ``y0`` (one gradient + LMO).

    With the real gap known at iteration 0, the Section 6.1 certificates can
    fire before any step is taken: a warm start that already vanishes
    (``f <= psi``) or is certifiably infeasible (``f - gap > psi``) makes the
    whole solve a no-op, so an IHB-warm solve whose closed form already
    settles the candidate runs no iteration at all."""
    maskf = mask.astype(Q.dtype)
    Qy = _mv(Q, y0)  # shared between f0 and grad: one matvec, not two
    f0 = (vdot(y0, Qy) + 2.0 * vdot(q, y0) + btb) * inv_m
    grad = (2.0 * inv_m) * (Qy + q) * maskf
    i, val = _fw_vertex(grad, mask, r)
    # <grad, w - y0> with w = val * e_i, without materializing w
    gap0 = vdot(grad, y0) - grad[i] * val
    return _FWState(y0, wp0, wm0, f0, gap0, jnp.asarray(0, jnp.int32))


def _fw_finish(state: _FWState) -> SolveResult:
    return SolveResult(y=state.y, f=state.f, gap=state.gap, iters=state.k)


def _cg_parts(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0):
    """Vanilla Frank-Wolfe (CG) with exact line search."""
    dtype = Q.dtype
    Lcap = Q.shape[0]
    inv_m = (1.0 / m).astype(dtype)
    r = jnp.asarray(cfg.tau - 1.0, dtype)
    maskf = mask.astype(dtype)
    if y0 is None:
        y0 = jnp.zeros((Lcap,), dtype)
    y0 = y0 * maskf

    def body(state: _FWState) -> _FWState:
        y = state.y
        grad = quad_grad(Q, q, inv_m, y) * maskf
        i, val = _fw_vertex(grad, mask, r)
        w = jnp.zeros_like(y).at[i].set(val)
        d = w - y
        gap = -vdot(grad, d)
        gamma = _line_search_quad(Q, inv_m, grad, d, jnp.asarray(1.0, dtype))
        y_new = y + gamma * d
        f = quad_f(Q, q, btb, inv_m, y_new)
        return _FWState(y_new, state.wp, state.wm, f, gap, state.k + 1)

    zero = jnp.zeros((Lcap,), dtype)
    state0 = _fw_state0(Q, q, btb, inv_m, y0, zero, zero, mask, r)
    return state0, partial(_fw_cond, cfg, psi), body, _fw_finish


def _active_extrema(grad, wp, wm, r):
    """Away vertex (argmax <grad, v>) and local FW vertex (argmin) over the
    active set.  Vertex +r e_i has score r*grad_i, -r e_i has -r*grad_i."""
    sp = r * grad
    sm = -r * grad
    away_p = jnp.where(wp > 0, sp, NEG_INF)
    away_m = jnp.where(wm > 0, sm, NEG_INF)
    ia_p, ia_m = jnp.argmax(away_p), jnp.argmax(away_m)
    away_is_p = away_p[ia_p] >= away_m[ia_m]
    loc_p = jnp.where(wp > 0, sp, -NEG_INF)
    loc_m = jnp.where(wm > 0, sm, -NEG_INF)
    il_p, il_m = jnp.argmin(loc_p), jnp.argmin(loc_m)
    local_is_p = loc_p[il_p] <= loc_m[il_m]
    return (away_is_p, ia_p, ia_m), (local_is_p, il_p, il_m)


def _signed_unit(i, sign_plus, r, Lcap, dtype):
    v = jnp.zeros((Lcap,), dtype)
    return v.at[i].set(jnp.where(sign_plus, r, -r))


def _pcg_parts(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0):
    """Pairwise Conditional Gradients (Lacoste-Julien & Jaggi 2015)."""
    dtype = Q.dtype
    Lcap = Q.shape[0]
    inv_m = (1.0 / m).astype(dtype)
    r = jnp.asarray(cfg.tau - 1.0, dtype)
    maskf = mask.astype(dtype)
    if y0 is None:
        y0 = jnp.zeros((Lcap,), dtype)
    y0 = y0 * maskf
    wp0, wm0 = _decompose_point(y0, r, mask)

    def body(state: _FWState) -> _FWState:
        y, wp, wm = state.y, state.wp, state.wm
        grad = quad_grad(Q, q, inv_m, y) * maskf
        # global FW vertex
        iw, val = _fw_vertex(grad, mask, r)
        w_plus = val > 0
        w_vec = _signed_unit(iw, w_plus, r, Lcap, dtype)
        # away vertex over active set
        (a_is_p, ia_p, ia_m), _ = _active_extrema(grad, wp, wm, r)
        ia = jnp.where(a_is_p, ia_p, ia_m)
        a_vec = _signed_unit(ia, a_is_p, r, Lcap, dtype)
        a_weight = jnp.where(a_is_p, wp[ia], wm[ia])
        d = w_vec - a_vec
        gap = -vdot(grad, w_vec - y)  # FW gap for stopping
        gamma = _line_search_quad(Q, inv_m, grad, d, a_weight)
        # move weight gamma from away to FW vertex
        wp = jnp.where(a_is_p, wp.at[ia].add(-gamma), wp)
        wm = jnp.where(a_is_p, wm, wm.at[ia].add(-gamma))
        wp = jnp.where(w_plus, wp.at[iw].add(gamma), wp)
        wm = jnp.where(w_plus, wm, wm.at[iw].add(gamma))
        wp = jnp.maximum(wp, 0.0)
        wm = jnp.maximum(wm, 0.0)
        y_new = _weights_to_point(wp, wm, r)
        f = quad_f(Q, q, btb, inv_m, y_new)
        return _FWState(y_new, wp, wm, f, gap, state.k + 1)

    state0 = _fw_state0(Q, q, btb, inv_m, y0, wp0, wm0, mask, r)
    return state0, partial(_fw_cond, cfg, psi), body, _fw_finish


def _bpcg_parts(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0):
    """Blended Pairwise Conditional Gradients (Tsuji et al. 2021, Alg. 3).

    The local/global branch is select-based (both branches computed, one
    kept) rather than ``lax.cond`` so the body stays bit-stable under vmap;
    the selected branch's values are identical either way.
    """
    dtype = Q.dtype
    Lcap = Q.shape[0]
    inv_m = (1.0 / m).astype(dtype)
    r = jnp.asarray(cfg.tau - 1.0, dtype)
    maskf = mask.astype(dtype)
    if y0 is None:
        y0 = jnp.zeros((Lcap,), dtype)
    y0 = y0 * maskf
    wp0, wm0 = _decompose_point(y0, r, mask)

    def body(state: _FWState) -> _FWState:
        y, wp, wm = state.y, state.wp, state.wm
        grad = quad_grad(Q, q, inv_m, y) * maskf
        iw, val = _fw_vertex(grad, mask, r)
        w_plus = val > 0
        w_vec = _signed_unit(iw, w_plus, r, Lcap, dtype)
        (a_is_p, ia_p, ia_m), (s_is_p, is_p, is_m) = _active_extrema(grad, wp, wm, r)
        ia = jnp.where(a_is_p, ia_p, ia_m)
        a_vec = _signed_unit(ia, a_is_p, r, Lcap, dtype)
        a_weight = jnp.where(a_is_p, wp[ia], wm[ia])
        is_ = jnp.where(s_is_p, is_p, is_m)
        s_vec = _signed_unit(is_, s_is_p, r, Lcap, dtype)
        gap = -vdot(grad, w_vec - y)
        # Line 7: local pairwise step iff <grad, w - y> >= <grad, s - a>
        local = vdot(grad, w_vec - y) >= vdot(grad, s_vec - a_vec)

        # local pairwise step
        d_l = s_vec - a_vec
        gamma_l = _line_search_quad(Q, inv_m, grad, d_l, a_weight)
        wp_l = jnp.where(a_is_p, wp.at[ia].add(-gamma_l), wp)
        wm_l = jnp.where(a_is_p, wm, wm.at[ia].add(-gamma_l))
        wp_l = jnp.where(s_is_p, wp_l.at[is_].add(gamma_l), wp_l)
        wm_l = jnp.where(s_is_p, wm_l, wm_l.at[is_].add(gamma_l))
        y_l = y + gamma_l * d_l

        # global FW step
        d_g = w_vec - y
        gamma_g = _line_search_quad(Q, inv_m, grad, d_g, jnp.asarray(1.0, dtype))
        wp_g = wp * (1.0 - gamma_g)
        wm_g = wm * (1.0 - gamma_g)
        wp_g = jnp.where(w_plus, wp_g.at[iw].add(gamma_g), wp_g)
        wm_g = jnp.where(w_plus, wm_g, wm_g.at[iw].add(gamma_g))
        y_g = y + gamma_g * d_g

        y_new = jnp.where(local, y_l, y_g)
        wp_new = jnp.maximum(jnp.where(local, wp_l, wp_g), 0.0)
        wm_new = jnp.maximum(jnp.where(local, wm_l, wm_g), 0.0)
        f = quad_f(Q, q, btb, inv_m, y_new)
        return _FWState(y_new, wp_new, wm_new, f, gap, state.k + 1)

    state0 = _fw_state0(Q, q, btb, inv_m, y0, wp0, wm0, mask, r)
    return state0, partial(_fw_cond, cfg, psi), body, _fw_finish


@partial(jax.jit, static_argnames=("cfg",))
def solve_cg(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0=None) -> SolveResult:
    return _run_while(*_cg_parts(Q, q, btb, m, mask, psi, cfg, y0))


@partial(jax.jit, static_argnames=("cfg",))
def solve_pcg(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0=None) -> SolveResult:
    return _run_while(*_pcg_parts(Q, q, btb, m, mask, psi, cfg, y0))


@partial(jax.jit, static_argnames=("cfg",))
def solve_bpcg(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0=None) -> SolveResult:
    return _run_while(*_bpcg_parts(Q, q, btb, m, mask, psi, cfg, y0))


SOLVERS = {
    "agd": solve_agd,
    "cg": solve_cg,
    "pcg": solve_pcg,
    "bpcg": solve_bpcg,
}


def solve(Q, q, btb, m, mask, psi, cfg: OracleConfig, y0=None) -> SolveResult:
    return SOLVERS[cfg.name](Q, q, btb, m, mask, psi, cfg, y0)

