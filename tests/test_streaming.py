"""Out-of-core OAVI (repro.streaming): sources, streaming scaler, and the
chunked Gram-statistics fit.

The load-bearing properties:

* the streamed fit is *bit-exact* against the in-memory fit at matched
  capacity, for every chunk size that is a multiple of the canonical Gram
  block, for both the closed-form ``fast`` engine and the convex-oracle
  configs — and through the 4-device sharded path against the in-memory
  sharded fit (subprocess, like test_distributed);
* results are chunk-size invariant (identical bits across {256, 1024, 4096});
* the streaming scaler matches the in-memory scaler bit for bit on every
  dtype the transform threads;
* a warm streaming refit compiles nothing.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import api, streaming
from repro.core import oavi
from repro.core.oavi import OAVIConfig
from repro.core.transform import MinMaxScaler
from repro.data.synthetic import planted_source, planted_stream_tile, write_shards
from repro.kernels import ops as kernel_ops
from repro.streaming import (
    ArraySource,
    ScaledSource,
    ShardDirSource,
    StreamingMinMaxScaler,
    SyntheticSource,
    iter_chunks,
)

M = 3000


@pytest.fixture(scope="module")
def planted():
    """Raw planted-polynomial stream + its materialization + fitted scalers."""
    source = planted_source(M, n=3, seed=0)
    X_raw = np.asarray(source.read(0, M))
    scaler = StreamingMinMaxScaler(dtype="float32").fit_source(source, 1024)
    X = scaler.transform(X_raw)
    return source, X_raw, scaler, X


def _assert_models_bit_equal(a, b):
    assert a.book.terms == b.book.terms
    assert [g.term for g in a.generators] == [g.term for g in b.generators]
    for ga, gb in zip(a.generators, b.generators):
        assert np.array_equal(ga.coeffs, gb.coeffs), ga.term
        assert ga.mse == gb.mse


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------


def test_iter_chunks_pads_trailing_chunk():
    src = ArraySource(np.arange(10.0).reshape(5, 2))
    chunks = list(iter_chunks(src, 4))
    assert [c.shape for c, _ in chunks] == [(4, 2), (4, 2)]
    assert [v for _, v in chunks] == [4, 1]
    assert np.array_equal(chunks[1][0][1:], np.zeros((3, 2)))


def test_synthetic_source_chunking_invariant():
    """Reads are identical no matter how the row range is chunked — the
    property the planted tile generator is built for."""
    src = planted_source(10_000, n=3, seed=3)
    whole = src.read(0, 10_000)
    for rows in (256, 1024, 4096):
        got = np.concatenate(
            [c[:v] for c, v in iter_chunks(src, rows)], axis=0
        )
        assert np.array_equal(got, whole)
    # absolute-row determinism: a mid-stream read equals the slice
    assert np.array_equal(src.read(5000, 7000), whole[5000:7000])


def test_planted_tile_deterministic():
    a = planted_stream_tile(7, n=3, seed=11)
    b = planted_stream_tile(7, n=3, seed=11)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, planted_stream_tile(8, n=3, seed=11))


def test_shard_dir_source_round_trip(tmp_path, planted):
    source, X_raw, _, _ = planted
    path = str(tmp_path / "shards")
    meta = write_shards(path, source, shard_rows=1024)
    assert meta["num_shards"] == (M + 1023) // 1024
    sd = ShardDirSource(path)
    assert (sd.num_rows, sd.num_features) == (M, 3)
    assert np.array_equal(sd.read(0, M), X_raw.astype(np.float32))
    # cross-shard read
    assert np.array_equal(sd.read(1000, 2100), X_raw[1000:2100].astype(np.float32))


def test_shard_dir_rejects_wrong_format(tmp_path):
    import json

    (tmp_path / "meta.json").write_text(json.dumps({"format": "other"}))
    with pytest.raises(ValueError, match="repro.shards.v1"):
        ShardDirSource(str(tmp_path))


# ---------------------------------------------------------------------------
# streaming scaler
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_streaming_scaler_bit_exact_every_dtype(planted, dtype):
    """lo/scale statistics AND transformed outputs match the in-memory
    MinMaxScaler bit for bit on every dtype the transform threads."""
    source, X_raw, _, _ = planted
    ref = MinMaxScaler(dtype=dtype).fit(X_raw)
    for rows in (256, 1024, 4096):
        sc = StreamingMinMaxScaler(dtype=dtype).fit_source(source, rows)
        assert np.array_equal(sc.lo, ref.lo)
        assert np.array_equal(sc.scale, ref.scale)
        out_s = sc.transform(X_raw[:500])
        out_r = ref.transform(X_raw[:500])
        assert out_s.dtype == out_r.dtype
        assert np.array_equal(out_s, out_r)


def test_streaming_scaler_partial_fit_prefix_usable():
    rng = np.random.default_rng(0)
    X = rng.uniform(-3, 5, (100, 4))
    sc = StreamingMinMaxScaler()
    sc.partial_fit(X[:40])
    assert sc.scale is not None  # usable mid-stream
    sc.partial_fit(X[40:])
    ref = MinMaxScaler().fit(X)
    assert np.array_equal(sc.lo, ref.lo)
    assert np.array_equal(sc.scale, ref.scale)


def test_scaled_source_requires_fitted_scaler(planted):
    source = planted[0]
    with pytest.raises(ValueError, match="fitted"):
        ScaledSource(source, StreamingMinMaxScaler())


# ---------------------------------------------------------------------------
# streaming fit: bit-exactness and chunk-size invariance
# ---------------------------------------------------------------------------


def test_streaming_fit_bit_exact_fast_engine_all_chunk_sizes(planted):
    source, _, scaler, X = planted
    cfg = OAVIConfig(psi=0.005, engine="fast", ordering="none", cap_terms=64)
    ref = oavi.fit(X, cfg)
    scaled = ScaledSource(source, scaler)
    for rows in (256, 1024, 4096):
        _assert_models_bit_equal(streaming.fit(scaled, cfg, chunk_rows=rows), ref)


def test_streaming_fit_bit_exact_oracle_engine(planted):
    source, _, scaler, X = planted
    cfg = OAVIConfig(psi=0.005, engine="oracle", ihb=True, ordering="none",
                     cap_terms=64)
    ref = oavi.fit(X, cfg)
    scaled = ScaledSource(source, scaler)
    for rows in (512, 2048):
        _assert_models_bit_equal(streaming.fit(scaled, cfg, chunk_rows=rows), ref)


def test_streaming_fit_pearson_ordering_matches(planted):
    """The one-pass moment-based Pearson order reproduces the in-memory
    order on this data, and the resulting fit is bit-exact."""
    source, _, scaler, X = planted
    cfg = OAVIConfig(psi=0.005, engine="fast", ordering="pearson", cap_terms=64)
    ref = oavi.fit(X, cfg)
    mdl = streaming.fit(ScaledSource(source, scaler), cfg, chunk_rows=1024)
    assert np.array_equal(mdl.feature_perm, ref.feature_perm)
    _assert_models_bit_equal(mdl, ref)


def test_streaming_fit_warm_refit_zero_recompiles(planted):
    source, _, scaler, _ = planted
    cfg = OAVIConfig(psi=0.005, engine="fast", ordering="none", cap_terms=64)
    scaled = ScaledSource(source, scaler)
    first = streaming.fit(scaled, cfg, chunk_rows=1024)
    assert first.stats["recompiles"] >= 0  # cold count depends on cache state
    warm = streaming.fit(scaled, cfg, chunk_rows=1024)
    assert warm.stats["recompiles"] == 0
    assert warm.stats["streaming"]["chunk_rows"] == 1024
    assert warm.stats["streaming"]["num_chunks"] > 0


def test_streaming_fit_regrowth_matches_in_memory(planted):
    """Tiny initial capacity forces device-side regrowth in both paths."""
    source, _, scaler, X = planted
    cfg = OAVIConfig(psi=0.0005, engine="fast", ordering="none", cap_terms=8,
                     max_degree=3)
    ref = oavi.fit(X, cfg)
    mdl = streaming.fit(ScaledSource(source, scaler), cfg, chunk_rows=512)
    assert mdl.stats["regrowths"] == ref.stats["regrowths"] > 0
    _assert_models_bit_equal(mdl, ref)


def test_streaming_fit_rejects_bad_chunk_rows(planted):
    source, _, scaler, _ = planted
    scaled = ScaledSource(source, scaler)
    for bad in (100, 128, 384):
        with pytest.raises(ValueError, match="chunk_rows"):
            streaming.fit(scaled, OAVIConfig(), chunk_rows=bad)


@settings(max_examples=5, deadline=None)
@given(st.sampled_from([256, 512, 1024, 2048, 4096]))
def test_streaming_fit_chunk_size_invariance_property(chunk_rows):
    """Hypothesis sweep: every legal chunk size produces identical bits."""
    source = planted_source(1500, n=3, seed=5)
    scaler = StreamingMinMaxScaler(dtype="float32").fit_source(source, 512)
    cfg = OAVIConfig(psi=0.005, engine="fast", ordering="none", cap_terms=64)
    ref = oavi.fit(scaler.transform(source.read(0, 1500)), cfg)
    mdl = streaming.fit(ScaledSource(source, scaler), cfg, chunk_rows=chunk_rows)
    _assert_models_bit_equal(mdl, ref)


def test_streaming_fit_classes_bit_exact_multi_engine():
    """Streaming multi-class fit (one vmapped stats step per degree, no row
    padding) is bit-exact against per-class in-memory fits — for the fast
    engine AND the oracle engines, their solvers under the vmap."""
    from repro.core.oracles import OracleConfig

    sizes = [1500, 900, 1200]
    sources = [planted_source(m, n=3, seed=40 + i) for i, m in enumerate(sizes)]
    scalers = [
        StreamingMinMaxScaler(dtype="float32").fit_source(s, 512) for s in sources
    ]
    scaled = [ScaledSource(s, sc) for s, sc in zip(sources, scalers)]
    Xs = [sc.transform(np.asarray(s.read(0, m)))
          for s, sc, m in zip(sources, scalers, sizes)]

    configs = [
        OAVIConfig(psi=0.005, engine="fast", ordering="none", cap_terms=64),
        OAVIConfig(psi=0.005, engine="oracle", solver=OracleConfig(name="bpcg"),
                   ihb=True, ordering="none", cap_terms=64),
    ]
    for cfg in configs:
        models = streaming.fit_classes(scaled, cfg, chunk_rows=512)
        for X, mdl in zip(Xs, models):
            _assert_models_bit_equal(mdl, oavi.fit(X, cfg))
        assert all(m.stats["class_batch"]["streaming"] for m in models)
        assert all(m.stats["class_batch"]["m_cap"] is None for m in models)
        warm = streaming.fit_classes(scaled, cfg, chunk_rows=512)
        assert warm[0].stats["recompiles"] == 0


def test_api_fit_classes_streaming_route():
    """api.fit_classes with chunk_rows routes through the streaming class
    batch and tags stats accordingly."""
    rng = np.random.default_rng(3)
    Xs = [rng.uniform(0, 1, (m, 3)).astype(np.float32) for m in (700, 500)]
    models = api.fit_classes(Xs, "oavi:fast", psi=0.005, cap_terms=64,
                             chunk_rows=256)
    assert all(m.stats["api"]["streaming"] for m in models)
    assert all(m.stats["api"]["class_batch"] for m in models)
    for X, mdl in zip(Xs, models):
        ref = api.fit(X, "oavi:fast", psi=0.005, cap_terms=64)
        assert mdl.book.terms == ref.book.terms
        assert [g.term for g in mdl.generators] == [g.term for g in ref.generators]


def test_gram_accumulate_chunked_equals_one_shot():
    """The kernel-level contract: carrying the accumulator across row chunks
    is bit-identical to one call over the concatenated rows."""
    rng = np.random.default_rng(0)
    m, L, n, K = 2048, 16, 4, 8
    A = rng.uniform(0, 1, (m, L)).astype(np.float32)
    X = rng.uniform(0, 1, (m, n)).astype(np.float32)
    parents = rng.integers(0, L, K).astype(np.int32)
    vars_ = rng.integers(0, n, K).astype(np.int32)
    one_shot = kernel_ops.gram_accumulate(A, X, parents, vars_)
    for rows in (256, 512, 1024):
        acc = None
        for lo in range(0, m, rows):
            acc = kernel_ops.gram_accumulate(
                A[lo : lo + rows], X[lo : lo + rows], parents, vars_, acc=acc
            )
        for a, b in zip(acc, one_shot):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the interpret-mode Pallas kernel implements the same reduction
    ql_i, c_i = kernel_ops.gram_accumulate(A, X, parents, vars_, interpret=True)
    assert np.array_equal(np.asarray(ql_i), np.asarray(one_shot[0]))
    assert np.array_equal(np.asarray(c_i), np.asarray(one_shot[1]))


# ---------------------------------------------------------------------------
# api / pipeline integration
# ---------------------------------------------------------------------------


def test_api_fit_source_dispatch(planted):
    source, _, scaler, X = planted
    cfg_kw = dict(psi=0.005, ordering="none", cap_terms=64)
    ref = api.fit(X, "oavi:fast", backend="local", **cfg_kw)
    mdl = api.fit(
        ScaledSource(source, scaler), "oavi:fast", backend="local",
        chunk_rows=1024, **cfg_kw
    )
    assert mdl.stats["api"]["streaming"] is True
    _assert_models_bit_equal(mdl, ref)
    # explicit source= kwarg is equivalent
    mdl2 = api.fit(
        None, "oavi:fast", backend="local",
        source=ScaledSource(source, scaler), chunk_rows=1024, **cfg_kw
    )
    _assert_models_bit_equal(mdl2, ref)


def test_api_fit_source_rejects_non_oavi(planted):
    source, _, scaler, _ = planted
    with pytest.raises(ValueError, match="OAVI only"):
        api.fit(ScaledSource(source, scaler), "vca")


def test_classifier_streaming_chunk_rows_bit_identical(appc_small):
    """PipelineConfig(chunk_rows=...) routes per-class fits out-of-core and
    reproduces the in-memory classifier exactly (class_batch is bypassed, so
    compare against class_batch='off')."""
    from repro.core.pipeline import PipelineConfig, VanishingIdealClassifier

    Xtr, ytr, Xte, yte = appc_small
    kw = dict(method="fast", psi=0.01, oavi_kw={"cap_terms": 64, "ordering": "none"})
    ref = VanishingIdealClassifier(PipelineConfig(class_batch="off", **kw))
    ref.fit(Xtr, ytr)
    clf = VanishingIdealClassifier(PipelineConfig(chunk_rows=512, **kw))
    clf.fit(Xtr, ytr)
    for a, b in zip(clf.models, ref.models):
        _assert_models_bit_equal(a, b)
    assert np.array_equal(clf.predict(Xte), ref.predict(Xte))


def test_classifier_streaming_save_load_round_trip(appc_small, tmp_path):
    from repro.core.pipeline import PipelineConfig, VanishingIdealClassifier

    Xtr, ytr, Xte, _ = appc_small
    clf = VanishingIdealClassifier(
        PipelineConfig(method="fast", psi=0.01, chunk_rows=512,
                       oavi_kw={"cap_terms": 64})
    )
    clf.fit(Xtr, ytr)
    path = str(tmp_path / "clf")
    clf.save(path)
    loaded = VanishingIdealClassifier.load(path)
    assert loaded.config.chunk_rows == 512
    assert np.array_equal(loaded.predict(Xte), clf.predict(Xte))


# ---------------------------------------------------------------------------
# memory accounting (satellite)
# ---------------------------------------------------------------------------


def test_fit_stats_record_memory(planted):
    """peak_bytes only where the device allocator reports it (gracefully
    absent on CPU); live-array accounting always measured."""
    source, _, scaler, X = planted
    cfg = OAVIConfig(psi=0.005, engine="fast", ordering="none", cap_terms=64)
    mem = oavi.device_memory_stats()
    for stats in (oavi.fit(X, cfg).stats,
                  streaming.fit(ScaledSource(source, scaler), cfg).stats):
        if "peak_bytes_in_use" in mem:
            assert stats["peak_bytes"] > 0
        else:
            assert "peak_bytes" not in stats
        assert stats["live_bytes_peak"] > 0


def test_streaming_memory_stays_chunk_bounded(planted):
    """The streamed fit's live device footprint must not scale with m: at
    4x the rows it stays within 1.5x (the in-memory fit's A alone grows 4x)."""
    cfg = OAVIConfig(psi=0.005, engine="fast", ordering="none", cap_terms=64)
    peaks = []
    for m in (4096, 16384):
        src = planted_source(m, n=3, seed=2)
        sc = StreamingMinMaxScaler(dtype="float32").fit_source(src, 1024)
        mdl = streaming.fit(ScaledSource(src, sc), cfg, chunk_rows=1024)
        peaks.append(mdl.stats["live_bytes_peak"])
    assert peaks[1] <= 1.5 * peaks[0], peaks


# ---------------------------------------------------------------------------
# sharded streaming (subprocess: fake devices must not leak into the session)
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


def test_streaming_sharded_4_devices_bit_exact_subprocess():
    """Streaming over a 4-device mesh: each shard streams its local chunks,
    one psum per degree — bit-exact vs the in-memory sharded fit (same row
    partition, same blocked reduction, same collective)."""
    out = _run_sub("""
        import numpy as np, jax
        from repro.core import distributed
        from repro.core.oavi import OAVIConfig
        from repro import streaming
        from repro.streaming import ScaledSource, StreamingMinMaxScaler
        from repro.data.synthetic import planted_source

        m = 3001  # not divisible by 4 -> padded-span path
        src = planted_source(m, n=3, seed=0)
        sc = StreamingMinMaxScaler(dtype="float32").fit_source(src, 1024)
        X = sc.transform(src.read(0, m))
        mesh = jax.make_mesh((4,), ("data",))
        for cfg in (
            OAVIConfig(psi=0.005, engine="fast", ordering="none", cap_terms=64),
            OAVIConfig(psi=0.005, engine="oracle", ihb=True, ordering="none",
                       cap_terms=64),
        ):
            ref = distributed.fit(X, cfg, mesh=mesh)
            for rows in (256, 1024):
                mdl = streaming.fit(ScaledSource(src, sc), cfg,
                                    chunk_rows=rows, mesh=mesh)
                assert mdl.book.terms == ref.book.terms
                for ga, gb in zip(mdl.generators, ref.generators):
                    assert np.array_equal(ga.coeffs, gb.coeffs), (cfg.engine, rows)
            warm = streaming.fit(ScaledSource(src, sc), cfg,
                                 chunk_rows=1024, mesh=mesh)
            assert warm.stats["recompiles"] == 0
        print("OK")
    """)
    assert "OK" in out
