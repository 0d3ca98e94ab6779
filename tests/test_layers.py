"""Layer-level unit tests: RoPE/M-RoPE, RMSNorm, hints, SSM primitives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import hints, layers, ssm


def test_rms_norm_unit_scale():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 3, 64)) * 7.0, jnp.float32)
    y = layers.rms_norm(x, jnp.ones(64))
    rms = np.sqrt(np.mean(np.asarray(y) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)


def test_rope_preserves_norm_and_relativity():
    rng = np.random.default_rng(1)
    B, S, H, dh = 1, 8, 2, 32
    x = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    y = layers.apply_rope(x, pos, 1e4)
    # rotation preserves norms
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)
    # relative property: <R_m q, R_n k> depends only on (m - n)
    q = jnp.asarray(rng.standard_normal((1, 1, 1, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 1, dh)), jnp.float32)
    def dot_at(m, n):
        qa = layers.apply_rope(q, jnp.asarray([[m]]), 1e4)
        ka = layers.apply_rope(k, jnp.asarray([[n]]), 1e4)
        return float(jnp.sum(qa * ka))
    assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-3
    assert abs(dot_at(5, 3) - dot_at(5, 0)) > 1e-4  # different offsets differ


def test_mrope_text_mode_equals_rope():
    """With t=h=w=index, M-RoPE must reduce to standard RoPE."""
    rng = np.random.default_rng(2)
    B, S, H, dh = 1, 6, 2, 32
    x = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = layers.apply_rope(x, pos, 1e4)
    got = layers.apply_mrope(x, layers.text_mrope_positions(pos), 1e4, (4, 6, 6))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_mrope_distinct_axes_differ():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 4, 1, 32)), jnp.float32)
    pos3 = jnp.zeros((1, 4, 3), jnp.int32).at[..., 1].set(jnp.arange(4)[None])
    pos3b = jnp.zeros((1, 4, 3), jnp.int32).at[..., 2].set(jnp.arange(4)[None])
    a = layers.apply_mrope(x, pos3, 1e4, (4, 6, 6))
    b = layers.apply_mrope(x, pos3b, 1e4, (4, 6, 6))
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_hints_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = hints.constrain(x, "data", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert hints.batch_axes() == ()


def test_hints_active_under_mesh():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        sizes = hints.axis_sizes()
        assert sizes == {"data": 1, "model": 1}
        x = jnp.ones((4, 8))
        y = hints.constrain(x, "data", ("model?", 8))
        assert y.shape == x.shape
        # unknown axes are dropped, not errors
        z = hints.constrain(x, "nonexistent", None)
        assert z.shape == x.shape


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 100))
def test_mamba_chunked_scan_matches_sequential(seed):
    """The chunked associative scan == naive sequential recurrence."""
    rng = np.random.default_rng(seed)
    B, T, di, ds = 1, 16, 4, 3
    dA = jnp.asarray(rng.uniform(0.5, 1.0, (B, T, di, ds)), jnp.float32)
    dBx = jnp.asarray(rng.standard_normal((B, T, di, ds)) * 0.1, jnp.float32)
    C = jnp.asarray(rng.standard_normal((B, T, ds)), jnp.float32)
    got = np.asarray(ssm._ssm_scan_chunked(dA, dBx, C, chunk=4))
    # naive reference
    h = np.zeros((B, di, ds), np.float32)
    want = np.zeros((B, T, di), np.float32)
    for t in range(T):
        h = np.asarray(dA)[:, t] * h + np.asarray(dBx)[:, t]
        want[:, t] = (h * np.asarray(C)[:, t][:, None, :]).sum(-1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_mlstm_chunked_matches_stepwise():
    """Chunked mLSTM == one-token recurrence applied sequentially."""
    rng = np.random.default_rng(7)
    B, H, T, dh = 1, 2, 8, 4
    q = jnp.asarray(rng.standard_normal((B, H, T, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, T, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, T, dh)), jnp.float32)
    li = jnp.asarray(rng.standard_normal((B, H, T)), jnp.float32)
    lf = jnp.asarray(np.log(rng.uniform(0.5, 0.99, (B, H, T))), jnp.float32)
    got = np.asarray(ssm._mlstm_chunk_scan(q, k, v, li, lf, chunk=4))
    # stepwise reference (stabilized recurrence)
    C = np.zeros((B, H, dh, dh)); n = np.zeros((B, H, dh)); m = np.full((B, H), -1e30)
    scale = 1 / np.sqrt(dh)
    want = np.zeros((B, H, T, dh))
    qn, kn, vn = np.asarray(q), np.asarray(k), np.asarray(v)
    lin, lfn = np.asarray(li), np.asarray(lf)
    for t in range(T):
        m_new = np.maximum(lfn[:, :, t] + m, lin[:, :, t])
        i_p = np.exp(lin[:, :, t] - m_new)
        f_p = np.exp(lfn[:, :, t] + m - m_new)
        C = f_p[..., None, None] * C + i_p[..., None, None] * (
            kn[:, :, t][..., :, None] * vn[:, :, t][..., None, :])
        n = f_p[..., None] * n + i_p[..., None] * kn[:, :, t]
        num = np.einsum("bhd,bhde->bhe", qn[:, :, t] * scale, C)
        den = np.einsum("bhd,bhd->bh", qn[:, :, t] * scale, n)
        want[:, :, t] = num / np.maximum(np.abs(den), np.exp(-m_new))[..., None]
        m = m_new
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_swiglu_shapes():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((8, 12)), jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    y = layers.swiglu(x, w_in, w_out)
    assert y.shape == (2, 8)
