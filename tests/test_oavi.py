"""System tests for OAVI: the paper's claims as executable assertions."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ihb, oavi, terms
from repro.core.oavi import OAVIConfig
from repro.core.oracles import OracleConfig


def _cfg(engine="fast", solver="bpcg", psi=0.005, **kw):
    return OAVIConfig(
        psi=psi, engine=engine, cap_terms=64,
        solver=OracleConfig(name=solver), **kw,
    )


def test_generators_vanish_on_train(planted_cube):
    model = oavi.fit(planted_cube, _cfg())
    assert model.num_G > 0
    mses = np.asarray(model.mse(planted_cube))
    assert mses.max() <= model.psi * (1 + 1e-3)


def test_thm_4_3_bound_holds(planted_cube):
    model = oavi.fit(planted_cube, _cfg())
    assert model.num_G + model.num_O <= model.stats["thm43_bound"]


def test_O_is_order_ideal(planted_cube):
    """Every divisor of a term in O is in O (OAVI invariant)."""
    model = oavi.fit(planted_cube, _cfg())
    idx = model.book.index
    for term in model.book.terms:
        for div in terms.immediate_divisors(term):
            assert div in idx


def test_engines_agree(planted_cube):
    """fast (closed-form IHB) == oracle engines on the same data."""
    ref = oavi.fit(planted_cube, _cfg(engine="fast"))
    for solver in ["agd", "cg", "bpcg"]:
        m = oavi.fit(planted_cube, _cfg(engine="oracle", solver=solver))
        assert [g.term for g in m.generators] == [g.term for g in ref.generators]
        assert m.book.terms == ref.book.terms


def test_wihb_produces_sparser_generators(planted_cube):
    dense = oavi.fit(planted_cube, _cfg(engine="oracle", solver="cg", ihb=True))
    sparse = oavi.fit(planted_cube, _cfg(engine="oracle", solver="bpcg",
                                         ihb=True, wihb=True))

    def spar(model):
        z = e = 0
        for g in model.generators:
            e += len(g.coeffs)
            z += int(np.sum(g.coeffs == 0.0))
        return z / max(e, 1)

    # WIHB re-solves accepted generators with BPCG from a cold start -> its
    # coefficient vectors can only be sparser or equal
    assert spar(sparse) >= spar(dense)
    # and the generators still vanish
    assert np.asarray(sparse.mse(planted_cube)).max() <= 0.005 * (1 + 1e-3)


def test_evaluation_on_unseen_data(planted_cube):
    """Theorem 4.2 machinery: G evaluates on new points of the same variety."""
    model = oavi.fit(planted_cube, _cfg())
    rng = np.random.default_rng(7)
    Z = rng.uniform(0, 1, (300, 4))
    Z[:, 3] = np.clip(Z[:, 0] * Z[:, 1], 0, 1)  # noiseless variety points
    mses = np.asarray(model.mse(Z))
    assert mses.max() < 0.05  # out-sample vanishing (paper's Theorem 6)


def test_pearson_ordering_makes_output_permutation_invariant(planted_cube):
    """Section 5: with Pearson ordering the output is independent of the
    initial feature permutation."""
    rng = np.random.default_rng(3)
    perm = rng.permutation(planted_cube.shape[1])
    a = oavi.fit(planted_cube, _cfg(ordering="pearson"))
    b = oavi.fit(planted_cube[:, perm], _cfg(ordering="pearson"))
    assert a.num_G == b.num_G and a.num_O == b.num_O
    # generator evaluation agrees on common points (up to fp noise)
    np.testing.assert_allclose(
        np.sort(np.abs(np.asarray(a.evaluate_G(planted_cube)))),
        np.sort(np.abs(np.asarray(b.evaluate_G(planted_cube[:, perm])))),
        rtol=5e-2, atol=5e-3,
    )


def test_without_ordering_output_depends_on_permutation(planted_cube):
    """The problem Section 5 fixes: no ordering -> permutation-sensitive."""
    rng = np.random.default_rng(3)
    perm = np.array([3, 0, 1, 2])
    a = oavi.fit(planted_cube, _cfg(ordering="none"))
    b = oavi.fit(planted_cube[:, perm], _cfg(ordering="none"))
    lead_a = {g.term for g in a.generators}
    lead_b = {g.term for g in b.generators}
    assert lead_a != lead_b or a.book.terms != b.book.terms


def test_psi_zero_like_behaviour_small_psi(planted_cube):
    """Tiny psi -> more terms in O, deeper degrees (no early acceptance)."""
    loose = oavi.fit(planted_cube, _cfg(psi=0.05))
    tight = oavi.fit(planted_cube, dataclasses.replace(_cfg(psi=0.0005), max_degree=4))
    assert tight.num_O >= loose.num_O


def test_capacity_growth():
    """cap_terms smaller than |O| triggers regrowth, not failure."""
    rng = np.random.default_rng(0)
    X = rng.uniform(0, 1, (400, 5))
    cfg = dataclasses.replace(_cfg(psi=0.001), cap_terms=8, max_degree=3)
    model = oavi.fit(X, cfg)
    assert model.num_G + model.num_O > 8
    assert model.stats["regrowths"] > 0


# -- kernel-fused degree step, slimmed IHB state, wavefront evaluation ------


def test_degree_step_parity_pallas_interpret(planted_cube):
    """The Pallas gram kernel (interpret mode) and the jnp gather fallback
    produce the same model — structure exact, coefficients bit-exact (m fits
    one kernel block, so both paths run the identical fp32 matmul)."""
    X = planted_cube[:256]
    jnp_cfg = dataclasses.replace(_cfg(), kernel="jnp", ordering="none")
    int_cfg = dataclasses.replace(_cfg(), kernel="interpret", ordering="none")
    a = oavi.fit(X, jnp_cfg)
    b = oavi.fit(X, int_cfg)
    assert a.book.terms == b.book.terms
    assert [g.term for g in a.generators] == [g.term for g in b.generators]
    for ga, gb in zip(a.generators, b.generators):
        assert np.array_equal(ga.coeffs, gb.coeffs)
        assert ga.mse == gb.mse


@pytest.mark.parametrize("kernel,expect", [
    ("jnp", {"use_pallas": False}),
    ("interpret", {"interpret": True}),
    ("auto", {}),
])
def test_kernel_config_reaches_ihb_update(planted_cube, monkeypatch, kernel, expect):
    """OAVIConfig.kernel steers the IHB update as well as the Gram op, and
    the update receives the warm start's N q instead of recomputing it."""
    from repro.kernels import ops

    seen = []
    real = ops.ihb_update

    def spy(N, q, btb, ell, **kw):
        seen.append(kw)
        return real(N, q, btb, ell, **kw)

    monkeypatch.setattr(ops, "ihb_update", spy)
    cfg = dataclasses.replace(_cfg(), kernel=kernel, ordering="none")
    model = oavi.fit(
        planted_cube[:256], cfg,
        _degree_step_factory=lambda: oavi._make_degree_step(cfg),
    )
    assert model.num_O > 1 and seen  # something was appended
    for kw in seen:
        assert kw.pop("u") is not None
        assert kw == expect


def test_gram_fallback_bit_exact_vs_inline_matmul(planted_cube):
    """ops.gram_update's gather fallback == the pre-PR inline formulation
    ``(A^T B, B^T B)`` with gathered candidate columns, bit for bit."""
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.uniform(0, 1, (300, 16)), jnp.float32)
    X = jnp.asarray(planted_cube[:300], jnp.float32)
    parents = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
    vars_ = jnp.asarray(rng.integers(0, 4, 8), jnp.int32)
    B = jnp.take(A, parents, axis=1) * jnp.take(X, vars_, axis=1)
    QL, C = ops.gram_update(A, X, parents, vars_, use_pallas=False)
    assert np.array_equal(np.asarray(QL), np.asarray(A.T @ B))
    assert np.array_equal(np.asarray(C), np.asarray(B.T @ B))


def test_slimmed_ihb_matches_full_state():
    """The slimmed state (only N maintained) appends bit-identically to the
    full 3-factor state's N — the pre-PR per-candidate work was 3x this."""
    rng = np.random.default_rng(0)
    Lcap, m = 16, 200
    cols = [np.ones(m)]
    full = ihb.init_state(Lcap, jnp.asarray(1.0, jnp.float32), jnp.float32)
    slim = ihb.init_state(
        Lcap, jnp.asarray(1.0, jnp.float32), jnp.float32, factors=("n",)
    )
    assert slim.AtA is None and slim.R is None
    assert full.AtA is not None and full.R is not None
    for j in range(1, 7):
        b = rng.uniform(0, 1, m)
        A = np.stack(cols, axis=1)
        q = np.zeros(Lcap, np.float32)
        q[:j] = A.T @ b / m
        btb = np.float32(b @ b / m)
        full = ihb.append_column(full, jnp.asarray(q), jnp.asarray(btb), jnp.asarray(j))
        slim = ihb.append_column(slim, jnp.asarray(q), jnp.asarray(btb), jnp.asarray(j))
        cols.append(b)
        assert np.array_equal(np.asarray(full.N), np.asarray(slim.N))
        assert slim.AtA is None and slim.R is None


def test_ihb_factors_for():
    assert ihb.factors_for("oracle", "inverse", True) == ("ata", "n")
    assert ihb.factors_for("oracle", "inverse", False) == ("ata",)
    assert ihb.factors_for("oracle", "chol", True) == ("ata", "r")
    assert ihb.factors_for("fast", "inverse", True) == ("n",)
    assert ihb.factors_for("fast", "chol", False) == ("r",)
    # the WIHB sparse re-solve runs BPCG regardless of engine -> needs AtA
    assert ihb.factors_for("fast", "inverse", True, wihb=True) == ("ata", "n")


def test_fast_engine_with_wihb_resolve(planted_cube):
    """engine='fast' + wihb: closed-form decisions, BPCG sparse re-solve of
    accepted generators — the slimmed state must still carry AtA for it."""
    model = oavi.fit(planted_cube, _cfg(wihb=True))
    ref = oavi.fit(planted_cube, _cfg())
    assert [g.term for g in model.generators] == [g.term for g in ref.generators]
    assert np.asarray(model.mse(planted_cube)).max() <= 0.005 * (1 + 1e-3)


def test_wavefront_evaluate_terms_bit_exact(planted_cube):
    """Degree-wavefront evaluation == the sequential fori_loop, bit for bit,
    on a fitted model's term book."""
    model = oavi.fit(planted_cube, _cfg(psi=0.0005))
    parents, vars_ = model.term_arrays()
    rng = np.random.default_rng(11)
    Z = jnp.asarray(rng.uniform(0, 1, (500, 4)), jnp.float32)
    wave = np.asarray(oavi.evaluate_terms(Z, parents, vars_))
    seq = np.asarray(
        oavi.evaluate_terms_sequential(Z, jnp.asarray(parents), jnp.asarray(vars_))
    )
    assert np.array_equal(wave, seq)


def test_evaluate_terms_traced_indices_fall_back(planted_cube):
    """evaluate_terms still works with traced index arrays (inside jit)."""
    model = oavi.fit(planted_cube, _cfg())
    parents, vars_ = model.term_arrays()
    Z = jnp.asarray(planted_cube[:100], jnp.float32)

    fn = jax.jit(lambda z, p, v: oavi.evaluate_terms(z, p, v))
    traced = np.asarray(fn(Z, jnp.asarray(parents), jnp.asarray(vars_)))
    direct = np.asarray(oavi.evaluate_terms(Z, parents, vars_))
    assert np.array_equal(traced, direct)


def test_recompile_regression():
    """Zero-recompile guarantee: a fit that forces two capacity regrowths
    compiles at most once per (Lcap, Kcap) bucket, and a warm refit with the
    same config and shapes compiles nothing."""
    rng = np.random.default_rng(0)
    X = rng.uniform(0, 1, (2000, 6)).astype(np.float32)
    cfg = OAVIConfig(
        psi=1e-6, engine="fast", cap_terms=32, max_degree=3, ordering="none"
    )
    model = oavi.fit(X, cfg)
    assert model.stats["regrowths"] >= 2
    # one compile per shape bucket, at most (buckets can be skipped when a
    # degree grows the capacity twice before its single step)
    assert model.stats["recompiles"] <= 3
    assert model.stats["recompiles"] >= 1
    warm = oavi.fit(X, cfg)
    assert warm.stats["recompiles"] == 0
    assert warm.book.terms == model.book.terms


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000), st.integers(2, 4),
       st.sampled_from([0.05, 0.01, 0.005]))
def test_property_invariants_random_data(seed, n, psi):
    """Properties on random data: vanishing, bound, order-ideal, determinism."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, (200, n))
    model = oavi.fit(X, _cfg(psi=psi, ordering="none"))
    assert model.num_G + model.num_O <= terms.theorem_4_3_size_bound(psi, n)
    if model.num_G:
        assert np.asarray(model.mse(X)).max() <= psi * (1 + 1e-2)
    # determinism
    again = oavi.fit(X, _cfg(psi=psi, ordering="none"))
    assert [g.term for g in again.generators] == [g.term for g in model.generators]
