"""Per-kernel validation: shape/dtype sweeps, interpret=True vs ref oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# gram_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,L,n,K,bm", [
    (256, 8, 4, 8, 128),
    (512, 32, 8, 16, 256),
    (1000, 16, 3, 32, 512),   # padded m
    (128, 64, 16, 8, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_gram_update_shapes(m, L, n, K, bm, dtype):
    rng = np.random.default_rng(m + L + K)
    A = jnp.asarray(rng.uniform(0, 1, (m, L)), dtype)
    X = jnp.asarray(rng.uniform(0, 1, (m, n)), dtype)
    parents = jnp.asarray(rng.integers(0, L, K), jnp.int32)
    vars_ = jnp.asarray(rng.integers(0, n, K), jnp.int32)
    QL_k, C_k = ops.gram_update(A, X, parents, vars_, bm=bm, interpret=True)
    Psel, Vsel = ops.selection_matrices(parents, vars_, L, n, dtype)
    QL_r, C_r = ref.gram_update_ref(A, X, Psel, Vsel)
    np.testing.assert_allclose(QL_k, QL_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(C_k, C_r, rtol=1e-5, atol=1e-5)


def test_gram_matches_direct_gather():
    """The one-hot-matmul formulation == direct gather semantics."""
    rng = np.random.default_rng(0)
    m, L, n, K = 300, 12, 5, 9
    A = jnp.asarray(rng.uniform(0, 1, (m, L)), jnp.float32)
    X = jnp.asarray(rng.uniform(0, 1, (m, n)), jnp.float32)
    parents = jnp.asarray(rng.integers(0, L, K), jnp.int32)
    vars_ = jnp.asarray(rng.integers(0, n, K), jnp.int32)
    B = ref.border_columns_ref(A, X, parents, vars_)
    QL, C = ops.gram_update(A, X, parents, vars_, bm=128, interpret=True)
    np.testing.assert_allclose(QL, np.asarray(A.T @ B), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(C, np.asarray(B.T @ B), rtol=1e-4, atol=1e-5)


def test_gram_gather_ref_bit_exact_vs_onehot_ref():
    """The fast gather fallback and the one-hot kernel spec are *bit*
    identical: a one-hot matmul row sums exactly one value plus hard zeros,
    so the candidate columns (and hence both Grams) match bit for bit."""
    rng = np.random.default_rng(7)
    m, L, n, K = 400, 24, 6, 17
    A = jnp.asarray(rng.uniform(0, 1, (m, L)), jnp.float32)
    X = jnp.asarray(rng.uniform(0, 1, (m, n)), jnp.float32)
    parents = jnp.asarray(rng.integers(0, L, K), jnp.int32)
    vars_ = jnp.asarray(rng.integers(0, n, K), jnp.int32)
    Psel, Vsel = ops.selection_matrices(parents, vars_, L, n, jnp.float32)
    g_gather = ref.gram_update_gather_ref(A, X, parents, vars_)
    g_onehot = ref.gram_update_ref(A, X, Psel, Vsel)
    for a, b in zip(g_gather, g_onehot):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the off-TPU ops dispatch routes to the gather formulation
    g_ops = ops.gram_update(A, X, parents, vars_, use_pallas=False)
    for a, b in zip(g_gather, g_ops):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_gram_property_symmetry_psd(seed):
    rng = np.random.default_rng(seed)
    m, L, n, K = 200, 8, 4, 8
    A = jnp.asarray(rng.uniform(0, 1, (m, L)), jnp.float32)
    X = jnp.asarray(rng.uniform(0, 1, (m, n)), jnp.float32)
    parents = jnp.asarray(rng.integers(0, L, K), jnp.int32)
    vars_ = jnp.asarray(rng.integers(0, n, K), jnp.int32)
    _, C = ops.gram_update(A, X, parents, vars_, bm=128, interpret=True)
    C = np.asarray(C)
    np.testing.assert_allclose(C, C.T, atol=1e-5)  # symmetric
    evals = np.linalg.eigvalsh(C)
    assert evals.min() > -1e-3  # PSD up to fp noise


# ---------------------------------------------------------------------------
# ihb_update
# ---------------------------------------------------------------------------


def _ihb_problem(L, ell, seed):
    """Padded inverse, Gram vector and squared norm of a random append."""
    rng = np.random.default_rng(seed)
    m = 200
    Araw = rng.uniform(0, 1, (m, ell)).astype(np.float32)
    G = Araw.T @ Araw / m + 1e-3 * np.eye(ell, dtype=np.float32)
    N = np.eye(L, dtype=np.float32)
    N[:ell, :ell] = np.linalg.inv(G)
    b = rng.uniform(0, 1, m).astype(np.float32)
    q = np.zeros(L, np.float32)
    q[:ell] = Araw.T @ b / m
    btb = np.float32(b @ b / m)
    return jnp.asarray(N), jnp.asarray(q), btb


@pytest.mark.parametrize(
    "L,ell", [(8, 3), (16, 7), (32, 20), (64, 1), (64, 40), (256, 100)]
)
def test_ihb_update_vs_ref(L, ell):
    N, q, btb = _ihb_problem(L, ell, L * 31 + ell)
    got = ops.ihb_update(N, q, btb, ell, interpret=True)
    want = ref.ihb_update_ref(N, q, btb, ell)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the caller's N q (the degree step's warm-start matvec) is used as is
    got_u = ops.ihb_update(N, q, btb, ell, u=N @ q, interpret=True)
    np.testing.assert_allclose(got_u, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("L", [64, 256])
def test_ihb_update_vmapped_vs_ref(L):
    """Class-batched use: the kernel under vmap, one append slot per lane."""
    probs = [_ihb_problem(L, ell, L + ell) for ell in (1, L // 4, L // 2 + 3)]
    N = jnp.stack([p[0] for p in probs])
    q = jnp.stack([p[1] for p in probs])
    btb = jnp.asarray([p[2] for p in probs])
    ell = jnp.asarray([1, L // 4, L // 2 + 3], jnp.int32)
    got = jax.vmap(lambda *a: ops.ihb_update(*a, interpret=True))(N, q, btb, ell)
    want = jax.vmap(ref.ihb_update_ref)(N, q, btb, ell)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("L", [64, 2048])
def test_ihb_row_block_bounds_vmem(L):
    from repro.kernels.ihb_update import row_block

    bl = row_block(L)
    assert L % bl == 0 and bl >= min(L, 8)
    assert bl * L * 4 <= 1 << 20  # one (bl, L) f32 block of N


# ---------------------------------------------------------------------------
# svm_grad
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,p,k,steps", [
    (3000, 12, 2, 1),    # Appendix C's features and classes
    (700, 506, 2, 1),    # credit's
    (5000, 506, 2, 3),   # credit's over several grid steps
    (1337, 9, 3, 1),     # one-vs-rest over three classes
])
def test_svm_grad_vs_ref(m, p, k, steps):
    """The kernel's (gW, gb) are the reference's to f32 rounding; the rows
    that pad m to the block carry label 0 and add nothing."""
    from repro.kernels.svm_grad import LANES, block_rows

    rng = np.random.default_rng(m + p + k)
    X = jnp.asarray(rng.uniform(0, 1, (m, p)), jnp.float32)
    Y = jnp.asarray(rng.choice([-1.0, 1.0], (m, k)), jnp.float32)
    W = jnp.asarray(rng.normal(0, 0.1, (p, k)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, k), jnp.float32)
    Xg, Yg = ops.svm_grad_operands(X, Y, interpret=True)
    br, R = block_rows(p, k, m)
    assert Xg.shape == (p, R, LANES) and Yg.shape == (k, R, LANES)
    assert R // br == steps and R * LANES > m
    assert not np.asarray(Yg).reshape(k, -1)[:, m:].any()
    gW, gb = ops.svm_grad(W, b, Xg, Yg, m, interpret=True)
    gW_r, gb_r = ref.squared_hinge_grad_ref(W, b, X, Y)
    assert gW.shape == (p, k) and gb.shape == (k,)
    eps = 1e-5  # f32 rounding of sums over a few thousand rows
    np.testing.assert_allclose(gW, gW_r, rtol=eps, atol=eps * np.abs(gW_r).max())
    np.testing.assert_allclose(gb, gb_r, rtol=eps, atol=eps * np.abs(gb_r).max())


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,S,d,bq,bk", [
    (1, 2, 2, 128, 32, 64, 64),
    (2, 4, 2, 256, 32, 64, 64),     # GQA group 2
    (2, 8, 1, 128, 16, 64, 32),     # MQA
    (1, 2, 2, 192, 32, 64, 64),     # padded seq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(B, Hq, Hkv, S, d, bq, bk, causal):
    rng = np.random.default_rng(B * 100 + S)
    q = jnp.asarray(rng.standard_normal((B, Hq, S, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, d)), jnp.float32)
    got = ops.multihead_attention(q, k, v, causal=causal, bq=bq, bk=bk, interpret=True)
    want = ops.multihead_attention(q, k, v, causal=causal, use_pallas=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_mla_vdim():
    """v head dim != qk head dim (MLA layout)."""
    rng = np.random.default_rng(5)
    B, H, S, d, dv = 1, 2, 128, 24, 16
    q = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, dv)), jnp.float32)
    got = ops.multihead_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    want = ops.multihead_attention(q, k, v, causal=True, use_pallas=False)
    assert got.shape == (B, H, S, dv)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(9)
    B, H, S, d = 1, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.bfloat16)
    got = ops.multihead_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    want = ops.multihead_attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


def test_flash_attention_causality():
    """Changing future tokens must not change past outputs."""
    rng = np.random.default_rng(11)
    B, H, S, d = 1, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.float32)
    out1 = ops.multihead_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    k2 = k.at[:, :, 100:].set(1000.0)
    v2 = v.at[:, :, 100:].set(-7.0)
    out2 = ops.multihead_attention(q, k2, v2, causal=True, bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(out1[:, :, :100], out2[:, :, :100], rtol=1e-5, atol=1e-5)
