"""End-to-end Algorithm 2 pipeline tests (classification quality + structure)."""

import numpy as np
import pytest

from repro.core.pipeline import PipelineConfig, VanishingIdealClassifier
from repro.core.svm import LinearSVM, LinearSVMConfig, PolySVM, PolySVMConfig


# thresholds mirror Table 3's ordering: IHB variants strongest; WIHB/ABM/VCA
# trade accuracy for sparsity / spurious vanishing (still far above chance)
_MIN_ACC = {"fast": 0.85, "cgavi-ihb": 0.85, "bpcgavi-wihb": 0.6, "abm": 0.7, "vca": 0.75}


@pytest.mark.parametrize("method", sorted(_MIN_ACC))
def test_pipeline_beats_chance_on_appc(appc_small, method):
    Xtr, ytr, Xte, yte = appc_small
    kw = {"cap_terms": 64} if method not in ("vca",) else {}
    clf = VanishingIdealClassifier(PipelineConfig(method=method, psi=0.005, oavi_kw=kw))
    clf.fit(Xtr, ytr)
    acc = clf.score(Xte, yte)
    assert acc > _MIN_ACC[method], f"{method}: test acc {acc}"


def test_pipeline_variants_agree_cgavi_agdavi(appc_small):
    """Table 3: CGAVI-IHB and AGDAVI-IHB produce identical outputs when the
    l1 constraint is slack (paper §6.2.2 'Similarity')."""
    Xtr, ytr, Xte, yte = appc_small
    accs = []
    for method in ["cgavi-ihb", "agdavi-ihb"]:
        clf = VanishingIdealClassifier(
            PipelineConfig(method=method, psi=0.005, oavi_kw={"cap_terms": 64}))
        clf.fit(Xtr, ytr)
        accs.append(clf.score(Xte, yte))
    assert abs(accs[0] - accs[1]) < 1e-6


def test_wihb_sparsity_table3(appc_small):
    """(SPAR): BPCGAVI-WIHB produces sparser generators than CGAVI-IHB."""
    Xtr, ytr, _, _ = appc_small
    sub = slice(0, 800)
    dense = VanishingIdealClassifier(
        PipelineConfig(method="cgavi-ihb", psi=0.005, oavi_kw={"cap_terms": 64}))
    dense.fit(Xtr[sub], ytr[sub])
    sparse = VanishingIdealClassifier(
        PipelineConfig(method="bpcgavi-wihb", psi=0.005, oavi_kw={"cap_terms": 64}))
    sparse.fit(Xtr[sub], ytr[sub])
    assert sparse.sparsity() >= dense.sparsity()


def test_transform_is_nonnegative(appc_small):
    Xtr, ytr, Xte, _ = appc_small
    clf = VanishingIdealClassifier(
        PipelineConfig(method="fast", psi=0.005, oavi_kw={"cap_terms": 64}))
    clf.fit(Xtr, ytr)
    ft = clf.transform(Xte)
    assert ft.shape[0] == Xte.shape[0]
    assert (ft >= 0).all()  # (FT) takes absolute values


def test_linear_svm_separable():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((400, 5))
    w = rng.standard_normal(5)
    y = (X @ w > 0).astype(int)
    svm = LinearSVM(LinearSVMConfig(lam=1e-5)).fit(X, y)
    assert svm.score(X, y) > 0.97


def test_linear_svm_l1_sparsity():
    """l1 penalty zeroes out nuisance features."""
    rng = np.random.default_rng(1)
    X = rng.standard_normal((500, 20))
    y = (X[:, 0] - X[:, 1] > 0).astype(int)
    strong = LinearSVM(LinearSVMConfig(lam=3e-2)).fit(X, y)
    W = strong.W
    used = np.abs(W).sum(axis=1) > 1e-6
    assert used[:2].all() and used.sum() <= 6


def test_poly_svm_learns_quadratic_boundary():
    rng = np.random.default_rng(2)
    X = rng.uniform(-1, 1, (600, 2))
    y = (X[:, 0] ** 2 + X[:, 1] ** 2 < 0.5).astype(int)
    svm = PolySVM(PolySVMConfig(degree=2, lam=1e-4, max_iter=3000)).fit(X, y)
    assert svm.score(X, y) > 0.9


def test_multiclass_one_vs_rest():
    rng = np.random.default_rng(3)
    centers = np.array([[0, 0], [3, 0], [0, 3]])
    X = np.concatenate([rng.normal(c, 0.4, (100, 2)) for c in centers])
    y = np.repeat([0, 1, 2], 100)
    svm = LinearSVM(LinearSVMConfig(lam=1e-4)).fit(X, y)
    assert svm.score(X, y) > 0.95
    assert set(svm.predict(X)) == {0, 1, 2}


def test_fista_gradient_kernel_matches_jnp_path():
    """FISTA with the svm_grad kernel (interpret mode) stops at the jnp
    path's iteration, on its (W, b) to f32 rounding."""
    import jax.numpy as jnp

    from repro.core.svm import _fista
    from repro.kernels import ops

    rng = np.random.default_rng(4)
    m, p, k = 700, 9, 3
    X = jnp.asarray(rng.standard_normal((m, p)), jnp.float32)
    y = rng.integers(0, k, m)
    Y = jnp.asarray(np.where(y[:, None] == np.arange(k), 1.0, -1.0), jnp.float32)
    lam, step, max_iter, tol = jnp.float32(1e-3), jnp.float32(0.05), 400, jnp.float32(1e-4)
    W, b, iters = _fista(X, Y, lam, step, max_iter, tol, m=m)
    Xg, Yg = ops.svm_grad_operands(X, Y, interpret=True)
    Wk, bk, iters_k = _fista(Xg, Yg, lam, step, max_iter, tol, m=m, interpret=True)
    assert int(iters_k) == int(iters) < max_iter
    np.testing.assert_allclose(Wk, W, rtol=0, atol=1e-5)
    np.testing.assert_allclose(bk, b, rtol=0, atol=1e-5)


def test_linear_svm_reports_jnp_gradient_off_tpu():
    from repro import obs

    fused = obs.registry().counter("svm/fused_grad_fits")
    before = fused.value
    rng = np.random.default_rng(5)
    X = rng.standard_normal((300, 4))
    svm = LinearSVM(LinearSVMConfig(max_iter=50)).fit(X, (X[:, 0] > 0).astype(int))
    assert svm.stats["grad_kernel"] == "jnp"
    assert fused.value == before
