"""Solver tests: AGD / CG / PCG / BPCG on OAVI's quadratic (CCOP) problems.

Under ``vmap`` (the class-batched and streaming fits) the batched
``while_loop`` runs until its slowest lane stops: a stopped lane's state must
not move, and each lane should reproduce its single solve's bits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.oracles import (
    SOLVERS as ORACLE_SOLVERS,
    OracleConfig,
    quad_f,
    solve_agd,
    solve_bpcg,
    solve_cg,
    solve_pcg,
)


def _problem(seed, m=200, ell=6, Lcap=8):
    rng = np.random.default_rng(seed)
    A = rng.uniform(0, 1, (m, ell)).astype(np.float32)
    b = rng.uniform(0, 1, m).astype(np.float32)
    Q = np.zeros((Lcap, Lcap), np.float32)
    q = np.zeros((Lcap,), np.float32)
    Q[:ell, :ell] = A.T @ A / m
    q[:ell] = A.T @ b / m
    btb = np.float32(b @ b / m)
    mask = np.arange(Lcap) < ell
    y_star = -np.linalg.solve(Q[:ell, :ell] + 1e-9 * np.eye(ell), q[:ell])
    f_star = (y_star @ Q[:ell, :ell] @ y_star + 2 * q[:ell] @ y_star + btb)
    return Q, q, btb, mask, y_star, f_star


CFG = {
    "agd": OracleConfig(name="agd", max_iter=5000, eps_frac=1e-3),
    "cg": OracleConfig(name="cg", max_iter=5000, eps_frac=1e-3, tau=1000.0),
    "pcg": OracleConfig(name="pcg", max_iter=5000, eps_frac=1e-3, tau=1000.0),
    "bpcg": OracleConfig(name="bpcg", max_iter=5000, eps_frac=1e-3, tau=1000.0),
}
SOLVERS = {"agd": solve_agd, "cg": solve_cg, "pcg": solve_pcg, "bpcg": solve_bpcg}


@pytest.mark.parametrize("name", ["agd", "cg", "pcg", "bpcg"])
def test_solver_reaches_near_optimum(name):
    Q, q, btb, mask, y_star, f_star = _problem(0)
    psi = jnp.asarray(0.005, jnp.float32)
    res = SOLVERS[name](
        jnp.asarray(Q), jnp.asarray(q), jnp.asarray(btb), jnp.asarray(1.0),
        jnp.asarray(mask), psi, CFG[name], None,
    )
    # solvers may stop early once f <= psi (paper's early termination);
    # otherwise they must be near f*
    f = float(res.f)
    assert f <= max(float(f_star) + 5e-3, 0.005 + 1e-6)


@pytest.mark.parametrize("name", ["cg", "pcg", "bpcg"])
def test_fw_iterates_stay_in_l1_ball(name):
    Q, q, btb, mask, *_ = _problem(1)
    cfg = OracleConfig(name=name, max_iter=300, eps_frac=1e-4, tau=2.0)
    res = SOLVERS[name](
        jnp.asarray(Q), jnp.asarray(q), jnp.asarray(btb), jnp.asarray(1.0),
        jnp.asarray(mask), jnp.asarray(1e-9, jnp.float32), cfg, None,
    )
    assert float(jnp.sum(jnp.abs(res.y))) <= cfg.tau - 1.0 + 1e-4


def test_warm_start_reduces_iterations():
    """IHB's premise: starting at the closed-form optimum needs ~no iters."""
    Q, q, btb, mask, y_star, f_star = _problem(2)
    psi = jnp.asarray(1e-9, jnp.float32)
    cfg = CFG["cg"]
    warm = np.zeros(Q.shape[0], np.float32)
    warm[: len(y_star)] = y_star
    cold = solve_cg(jnp.asarray(Q), jnp.asarray(q), jnp.asarray(btb),
                    jnp.asarray(1.0), jnp.asarray(mask), psi, cfg, None)
    hot = solve_cg(jnp.asarray(Q), jnp.asarray(q), jnp.asarray(btb),
                   jnp.asarray(1.0), jnp.asarray(mask), psi, cfg,
                   jnp.asarray(warm))
    assert int(hot.iters) <= int(cold.iters)
    assert int(hot.iters) <= 2


ALL_NAMES = ["agd", "cg", "pcg", "bpcg"]


def _assert_same_result(ref, lane, *, name=""):
    assert np.array_equal(np.asarray(ref.y), np.asarray(lane.y)), f"{name}: y"
    assert np.asarray(ref.f) == np.asarray(lane.f), f"{name}: f"
    assert np.asarray(ref.gap) == np.asarray(lane.gap), f"{name}: gap"
    assert int(ref.iters) == int(lane.iters), f"{name}: iters"


def _stack(probs, warms=None):
    k, Lcap = len(probs), probs[0][0].shape[0]
    y0 = jnp.zeros((k, Lcap), jnp.float32) if warms is None else jnp.asarray(warms)
    return tuple(jnp.stack([jnp.asarray(p[j]) for p in probs]) for j in range(4)) + (y0,)


def _batched(name, psi, cfg):
    def single(Q, q, btb, mask, y0):
        return ORACLE_SOLVERS[name](Q, q, btb, jnp.asarray(1.0), mask, psi, cfg, y0)

    return single, jax.jit(jax.vmap(single))


@pytest.mark.parametrize("name", ["cg", "pcg", "bpcg"])
def test_scheduled_budget_zero_warm_certificate(name):
    """The entry state carries the true FW gap: a warm start at the solution
    fires a certificate before the first iteration."""
    Q, q, btb, mask, y_star, f_star = _problem(5)
    warm = np.zeros(Q.shape[0], np.float32)
    warm[: len(y_star)] = y_star
    psi = jnp.asarray(float(f_star) + 1e-3, jnp.float32)  # warm start vanishes
    res = ORACLE_SOLVERS[name](
        jnp.asarray(Q), jnp.asarray(q), jnp.asarray(btb), jnp.asarray(1.0),
        jnp.asarray(mask), psi, CFG[name], jnp.asarray(warm),
    )
    assert int(res.iters) == 0
    assert float(res.f) <= float(psi)


@pytest.mark.parametrize("name", ALL_NAMES)
def test_scheduled_vmap_bit_identity(name):
    """A vmapped batch of k solves is bit-identical to the k single solves —
    the contract the class-batched fit rides on."""
    k = 4
    probs = [_problem(10 + i, m=120, ell=3 + i, Lcap=8) for i in range(k)]
    psi = jnp.asarray(1e-6, jnp.float32)
    cfg = OracleConfig(name=name, max_iter=256, eps_frac=1e-3, tau=10.0)
    single, batched = _batched(name, psi, cfg)
    args = _stack(probs)
    out = batched(*args)
    for i in range(k):
        ref = single(*(a[i] for a in args))
        lane = jax.tree_util.tree_map(lambda a: a[i], out)
        _assert_same_result(ref, lane, name=f"{name} lane={i}")


@pytest.mark.parametrize("name", ALL_NAMES)
def test_vmap_stopped_lane_keeps_its_state(name):
    """A lane that stops at once rides a long batched solve unchanged: its
    result beside cold lanes that iterate equals its result in a batch that
    stops at once."""
    probs = [_problem(30 + i, m=120, ell=5, Lcap=8) for i in range(3)]
    warms = np.zeros((3, 8), np.float32)
    warms[:, :5] = probs[0][4]
    psi = jnp.asarray(float(probs[0][5]) + 1e-3, jnp.float32)  # lane 0 vanishes
    _, batched = _batched(name, psi, CFG[name])
    mixed = batched(*_stack(probs, np.where(np.arange(3)[:, None] == 0, warms, 0.0)))
    alone = batched(*_stack([probs[0]] * 3, warms))
    assert int(mixed.iters[0]) == 0
    assert int(mixed.iters[1:].max()) > 0, "no lane iterated"
    first = jax.tree_util.tree_map(lambda a: a[0], alone)
    _assert_same_result(first, jax.tree_util.tree_map(lambda a: a[0], mixed), name=name)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_property_solvers_agree_near_optimum(seed):
    Q, q, btb, mask, y_star, f_star = _problem(seed, m=100, ell=4, Lcap=4)
    psi = jnp.asarray(1e-12, jnp.float32)  # force full optimization
    fs = []
    for name in ["agd", "bpcg"]:
        res = SOLVERS[name](
            jnp.asarray(Q), jnp.asarray(q), jnp.asarray(btb), jnp.asarray(1.0),
            jnp.asarray(np.ones(4, bool)), psi, CFG[name], None,
        )
        fs.append(float(res.f))
    assert abs(fs[0] - fs[1]) < 5e-3
    assert min(fs) >= float(f_star) - 5e-3  # cannot beat the true optimum
