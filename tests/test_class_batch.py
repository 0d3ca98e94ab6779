"""Class-batched OAVI tests: bit-exactness vs the sequential path, done
masking, bucket grouping, classifier integration, stats aggregation, and the
sharded (vmap-inside-shard_map) composition.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import api
from repro.core import class_batch, oavi
from repro.core.class_batch import class_buckets, fit_classes
from repro.core.oavi import OAVIConfig, class_batchable
from repro.core.pipeline import PipelineConfig, VanishingIdealClassifier
from repro.data.synthetic import _planted_class, random_cube, uci_like, train_test_split

CFG = OAVIConfig(psi=0.005, engine="fast", cap_terms=64)


def _classes(k, m, n=4, seed=0):
    return [
        np.clip(
            _planted_class(np.random.default_rng(seed + c), m, n, degree=2 + (c % 2)),
            0,
            1,
        ).astype(np.float32)
        for c in range(k)
    ]


def _assert_bit_exact(a: oavi.OAVIModel, b: oavi.OAVIModel):
    assert a.book.terms == b.book.terms
    assert [g.term for g in a.generators] == [g.term for g in b.generators]
    for ga, gb in zip(a.generators, b.generators):
        assert np.array_equal(ga.coeffs, gb.coeffs), ga.term
        assert ga.mse == gb.mse, ga.term


def _assert_structure(a: oavi.OAVIModel, b: oavi.OAVIModel, tol=1e-4):
    assert a.book.terms == b.book.terms
    assert [g.term for g in a.generators] == [g.term for g in b.generators]
    for ga, gb in zip(a.generators, b.generators):
        np.testing.assert_allclose(ga.coeffs, gb.coeffs, rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# core: batched vs sequential
# ---------------------------------------------------------------------------


def test_batchable_gate():
    assert class_batchable(CFG)
    # oracle and WIHB configs batch with their solvers under the vmap; only
    # the Cholesky inverse (batched triangular solves are not
    # vmap-bit-stable) stays sequential
    assert class_batchable(OAVIConfig(engine="oracle"))
    assert class_batchable(OAVIConfig(engine="fast", wihb=True))
    assert not class_batchable(OAVIConfig(engine="fast", inverse_engine="chol"))
    assert not class_batchable(OAVIConfig(engine="oracle", inverse_engine="chol"))
    with pytest.raises(ValueError):
        fit_classes([np.zeros((4, 2))], OAVIConfig(engine="fast", inverse_engine="chol"))


def test_batched_equals_sequential_bit_exact_equal_sizes():
    """Equal pow2 class sizes: no row padding, so the batched fit must
    reproduce the plain sequential fit bit for bit."""
    Xs = _classes(k=4, m=512)
    seq = [oavi.fit(X, CFG) for X in Xs]
    bat = fit_classes(Xs, CFG)
    assert all(m.num_G > 0 for m in bat)
    for s, b in zip(seq, bat):
        _assert_bit_exact(s, b)


def test_batched_uneven_sizes_matched_capacity():
    """Uneven sizes: structure-exact vs the unpadded sequential fit, and
    bit-exact vs the matched-capacity reference (same m_cap, k=1)."""
    sizes = [300, 500, 1003, 2048]
    Xs = [
        np.clip(_planted_class(np.random.default_rng(7 + i), m, 4), 0, 1).astype(
            np.float32
        )
        for i, m in enumerate(sizes)
    ]
    bat = fit_classes(Xs, CFG)
    m_cap = bat[0].stats["class_batch"]["m_cap"]
    assert m_cap == 2048
    for i, (X, b) in enumerate(zip(Xs, bat)):
        # vs unpadded sequential: structure exact; coefficients carry the fp
        # drift of the zero-extended Gram reduction amplified through
        # (A^T A)^{-1} (cf. the distributed psum tolerance)
        _assert_structure(oavi.fit(X, CFG), b, tol=5e-2)
        ref = fit_classes([X], CFG, m_cap=m_cap)[0]  # matched-capacity ref
        _assert_bit_exact(ref, b)


def test_single_class_equals_sequential():
    """k=1 (internally ridden with a discarded copy): bit-exact vs
    sequential when m is already the bucket size."""
    X = _classes(k=1, m=256)[0]
    _assert_bit_exact(oavi.fit(X, CFG), fit_classes([X], CFG)[0])


def test_done_masking_early_vs_late_termination():
    """One class terminates at degree 1 (all candidates vanish) while the
    other runs to max_degree: the finished class's lanes are no-ops and both
    results match their sequential fits exactly."""
    rng = np.random.default_rng(0)
    cfg = OAVIConfig(psi=1e-5, engine="fast", cap_terms=64, max_degree=3)
    X_const = (0.5 + 1e-4 * rng.standard_normal((256, 3))).astype(np.float32)
    X_deep = random_cube(m=256, n=3, seed=1)
    bat = fit_classes([X_const, X_deep], cfg)
    assert bat[0].stats["termination"] == "empty_border"
    assert bat[0].stats["degrees"] == [1]
    assert bat[1].stats["termination"] == "max_degree=3"
    assert bat[1].stats["degrees"] == [1, 2, 3]
    for X, b in zip([X_const, X_deep], bat):
        _assert_bit_exact(oavi.fit(X, cfg), b)


def test_batched_warm_refit_zero_recompiles():
    Xs = _classes(k=3, m=256, seed=11)
    cold = fit_classes(Xs, CFG)
    assert cold[0].stats["recompiles"] >= 0  # may be warm from other tests
    warm = fit_classes(Xs, CFG)
    assert warm[0].stats["recompiles"] == 0
    assert all(m.stats["recompiles"] == 0 for m in warm)


def test_gram_tiles_counted_per_degree():
    """``stats["gram_tiles"]`` and counter ``gram/tiles`` count the Gram
    kernel's output tiles at each degree's (Lcap, Kcap): one a degree at a
    narrow fit, several at the spam deployment's degree 2 (n = 57, a
    border of 1,653: Lcap = Kcap = 2048)."""
    from repro import obs
    from repro.kernels import ops

    counter = obs.registry().counter("gram/tiles")
    before = counter.value
    models = fit_classes(_classes(2, 300), CFG)
    degrees = len(models[0].stats["degree_times"])
    assert all(m.stats["gram_tiles"] == degrees for m in models)
    assert counter.value - before == degrees
    assert ops.gram_tiles(64, 64, 4) == 1
    assert ops.gram_tiles(2048, 2048, 57) == 16


def test_class_buckets_grouping():
    # greedy largest-first, padding <= 2x within a bucket
    assert class_buckets([512, 512, 512]) == {512: [0, 1, 2]}
    assert class_buckets([64, 70, 800]) == {1024: [2], 128: [0, 1]}
    assert class_buckets([100, 3000, 120, 2500]) == {4096: [1, 3], 128: [0, 2]}
    # every index appears exactly once
    buckets = class_buckets([5, 9, 17, 33, 65, 129])
    got = sorted(i for idxs in buckets.values() for i in idxs)
    assert got == list(range(6))


# ---------------------------------------------------------------------------
# api layer
# ---------------------------------------------------------------------------


def test_api_fit_classes_mixed_buckets_and_straggler():
    """Stragglers are folded into the nearest warm bucket, never sequential:
    [256, 250, 17] plans as ONE padded group, and every model reports its
    padding bill in stats['class_batch_padding']."""
    sizes = [256, 250, 17]
    Xs = [
        np.clip(_planted_class(np.random.default_rng(i), m, 4), 0, 1).astype(
            np.float32
        )
        for i, m in enumerate(sizes)
    ]
    models = api.fit_classes(Xs, "oavi:fast", psi=0.005)
    kinds = ["batched" if m.stats.get("class_batch") else "seq" for m in models]
    assert kinds == ["batched", "batched", "batched"]
    # class order is preserved
    for X, m in zip(Xs, models):
        assert m.stats["m"] == X.shape[0]
    for m in models:
        pad = m.stats["class_batch_padding"]
        assert pad["m_cap"] == 256
        assert pad["padded_rows"] == 256 - m.stats["m"]
        assert pad["group_rows"] == sum(sizes)
        assert 0.0 <= pad["waste"] < 1.0
    agg = api.aggregate_fit_stats(models)
    assert agg["class_batched"] == 3
    assert agg["class_batch_groups"] == 1
    # one shared group: its recompile count is counted exactly once
    assert agg["recompiles"] == models[0].stats["recompiles"]


def test_plan_class_groups():
    from repro.core.class_batch import plan_class_groups

    # near-boundary buckets merge within the padding budget
    assert plan_class_groups([256, 250, 17]) == [(256, [0, 1, 2])]
    # a lone class still gets folded (never a size-1 group)
    plans = plan_class_groups([1000, 900, 400, 40, 3])
    assert all(len(idxs) >= 2 for _, idxs in plans)
    assert sorted(i for _, idxs in plans for i in idxs) == list(range(5))
    # single class: one group is fine (fit_classes rides it with a copy)
    assert plan_class_groups([128]) == [(128, [0])]
    # far-apart buckets stay separate when merging would blow the pad limit
    plans = plan_class_groups([4096, 4000, 100, 90, 80])
    assert len(plans) == 2
    assert plans[0][1] == [0, 1] and plans[1][1] == [2, 3, 4]


def test_api_fit_list_dispatch_and_off():
    Xs = _classes(k=2, m=128, seed=3)
    models = api.fit(Xs, "oavi:fast", psi=0.005)
    assert len(models) == 2 and models[0].stats["api"]["class_batch"] is True
    off = api.fit_classes(Xs, "oavi:fast", psi=0.005, class_batch="off")
    assert all(m.stats.get("class_batch") is None for m in off)
    for a, b in zip(models, off):
        _assert_bit_exact(a, b)
    with pytest.raises(ValueError):
        api.fit_classes(Xs, "oavi:fast", class_batch="always")


def test_api_fit_classes_oracle_batched_and_abm_fallback():
    """Oracle-engine configs ride the batched path (their solvers under the
    vmap) bit-exactly; non-OAVI methods (abm) still fall back to
    sequential fits with identical results."""
    Xs = _classes(k=2, m=128, seed=5)
    auto = api.fit_classes(Xs, "oavi:cgavi-ihb", psi=0.005, cap_terms=64)
    off = api.fit_classes(
        Xs, "oavi:cgavi-ihb", psi=0.005, cap_terms=64, class_batch="off"
    )
    assert all(m.stats.get("class_batch") for m in auto)
    assert all(m.stats.get("class_batch") is None for m in off)
    for a, b in zip(auto, off):
        _assert_bit_exact(a, b)  # equal pow2 sizes: no row padding

    for method in ("abm",):
        auto = api.fit_classes(Xs, method, psi=0.005, cap_terms=64)
        off = api.fit_classes(Xs, method, psi=0.005, cap_terms=64, class_batch="off")
        assert all(m.stats.get("class_batch") is None for m in auto)
        for a, b in zip(auto, off):
            assert np.array_equal(
                np.asarray(a.transform(Xs[0])), np.asarray(b.transform(Xs[0]))
            )


def test_batched_oracle_engines_bit_exact():
    """Every oracle engine (and WIHB) through the batched path, bit-exact vs
    its sequential fit at matched (pow2, padding-free) sizes."""
    from repro.core.oracles import OracleConfig

    Xs = _classes(k=3, m=256, seed=21)
    configs = [
        OAVIConfig(psi=0.005, engine="oracle",
                   solver=OracleConfig(name="bpcg"), ihb=True, cap_terms=64),
        OAVIConfig(psi=0.005, engine="oracle",
                   solver=OracleConfig(name="cg"), ihb=False, cap_terms=64),
        OAVIConfig(psi=0.005, engine="oracle",
                   solver=OracleConfig(name="agd"), ihb=True, cap_terms=64),
        OAVIConfig(psi=0.005, engine="fast", wihb=True, cap_terms=64),
    ]
    for cfg in configs:
        seq = [oavi.fit(X, cfg) for X in Xs]
        bat = fit_classes(Xs, cfg)
        for s, b in zip(seq, bat):
            _assert_bit_exact(s, b)
        warm = fit_classes(Xs, cfg)
        assert warm[0].stats["recompiles"] == 0, cfg


# ---------------------------------------------------------------------------
# classifier integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def seeds_data():
    X, y = uci_like("seeds", seed=0)
    return train_test_split(X, y)


def test_classifier_class_batch_bit_identical(seeds_data):
    Xtr, ytr, Xte, yte = seeds_data
    on = VanishingIdealClassifier(
        PipelineConfig(method="fast", psi=0.005, class_batch="auto")
    ).fit(Xtr, ytr)
    off = VanishingIdealClassifier(
        PipelineConfig(method="fast", psi=0.005, class_batch="off")
    ).fit(Xtr, ytr)
    assert on.stats["class_batched"] == len(on.models)
    assert off.stats["class_batched"] == 0
    for a, b in zip(on.models, off.models):
        _assert_bit_exact(a, b)
    assert np.array_equal(on.predict(Xte), off.predict(Xte))


def test_classifier_phase_timings_and_aggregated_stats(seeds_data):
    Xtr, ytr, _, _ = seeds_data
    clf = VanishingIdealClassifier(PipelineConfig(method="fast", psi=0.005))
    clf.fit(Xtr, ytr)
    s = clf.stats
    for key in ("time_generators", "time_transform", "time_svm", "time_total"):
        assert s[key] >= 0.0
    assert s["time_total"] >= s["time_generators"] + s["time_transform"] + s["time_svm"] - 1e-6
    assert "recompiles" in s and "regrowths" in s
    assert len(s["per_class"]) == len(clf.models)


def test_classifier_warm_refit_zero_recompiles(seeds_data):
    """Regression: a warm multi-class refit through the batched path must
    compile nothing (shared global degree-step cache)."""
    Xtr, ytr, _, _ = seeds_data
    VanishingIdealClassifier(PipelineConfig(method="fast", psi=0.005)).fit(Xtr, ytr)
    warm = VanishingIdealClassifier(PipelineConfig(method="fast", psi=0.005)).fit(
        Xtr, ytr
    )
    assert warm.stats["class_batched"] == len(warm.models)
    assert warm.stats["recompiles"] == 0


def test_classifier_save_load_roundtrip_with_class_batch(seeds_data, tmp_path):
    Xtr, ytr, Xte, _ = seeds_data
    clf = VanishingIdealClassifier(
        PipelineConfig(method="fast", psi=0.005, class_batch="auto")
    ).fit(Xtr, ytr)
    path = str(tmp_path / "clf")
    clf.save(path)
    loaded = VanishingIdealClassifier.load(path)
    assert loaded.config.class_batch == "auto"
    assert np.array_equal(clf.predict(Xte), loaded.predict(Xte))


# ---------------------------------------------------------------------------
# sharded composition (subprocess so XLA fake devices don't leak)
# ---------------------------------------------------------------------------


def _run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.abspath("src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_class_batched_sharded_4_devices_subprocess():
    """vmap-inside-shard_map: class-batched fit over a 4-device data mesh
    matches the local class-batched fit (structure exact, coefficients to
    psum reduction-order noise) with zero recompiles when warm."""
    out = _run_sub("""
        import numpy as np, jax
        from repro.core import class_batch
        from repro.core.oavi import OAVIConfig
        from repro.data.synthetic import _planted_class
        cfg = OAVIConfig(psi=0.005, engine="fast", cap_terms=64)
        Xs = [np.clip(_planted_class(np.random.default_rng(c), 1003, 4), 0, 1)
              .astype(np.float32) for c in range(3)]
        local = class_batch.fit_classes(Xs, cfg)
        mesh = jax.make_mesh((4,), ("data",))
        shard = class_batch.fit_classes(Xs, cfg, mesh=mesh)
        for ml, ms in zip(local, shard):
            assert ml.book.terms == ms.book.terms
            assert [g.term for g in ml.generators] == [g.term for g in ms.generators]
            for gl, gs in zip(ml.generators, ms.generators):
                np.testing.assert_allclose(gl.coeffs, gs.coeffs, rtol=5e-3, atol=2e-3)
        warm = class_batch.fit_classes(Xs, cfg, mesh=mesh)
        assert warm[0].stats["recompiles"] == 0, warm[0].stats
        print("OK", [m.num_G for m in shard])
    """)
    assert "OK" in out


def test_api_fit_classes_sharded_backend_subprocess():
    out = _run_sub("""
        import numpy as np, jax
        from repro import api
        from repro.data.synthetic import _planted_class
        Xs = [np.clip(_planted_class(np.random.default_rng(c), 512, 4), 0, 1)
              .astype(np.float32) for c in range(2)]
        mesh = jax.make_mesh((4,), ("data",))
        models = api.fit_classes(Xs, "oavi:fast", psi=0.005,
                                 backend="sharded", mesh=mesh)
        assert all(m.stats["api"]["backend"] == "sharded" for m in models)
        assert all(m.stats.get("class_batch") for m in models)
        local = api.fit_classes(Xs, "oavi:fast", psi=0.005, backend="local")
        for ml, ms in zip(local, models):
            assert ml.book.terms == ms.book.terms
        print("OK")
    """)
    assert "OK" in out
