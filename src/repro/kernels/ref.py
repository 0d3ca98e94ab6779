"""Pure-jnp reference oracles for every Pallas kernel.

These define the semantics; the kernels must match them (tests sweep shapes
and dtypes and assert allclose against these, with the kernels run in
interpret=True mode on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The references compute at full f32 precision (TPU's default matmul
# precision rounds f32 operands to bf16).
_HIGHEST = jax.lax.Precision.HIGHEST


def gram_update_ref(A, X, Psel, Vsel):
    """(QL, C): candidate columns via one-hot selection, then both Grams."""
    B = jnp.matmul(A, Psel, precision=_HIGHEST) * jnp.matmul(X, Vsel, precision=_HIGHEST)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    return jnp.matmul(Af.T, Bf, precision=_HIGHEST), jnp.matmul(Bf.T, Bf, precision=_HIGHEST)


def gram_update_gather_ref(A, X, parents, vars_):
    """(QL, C) with the candidate columns built by direct gather.

    Bit-identical to :func:`gram_update_ref` (a one-hot matmul row sums
    exactly one nonzero entry plus exact zeros), but O(m*K) instead of
    O(m*L*K) column construction — the fast CPU/GPU fallback used by
    ``ops.gram_update`` off-TPU, where gathers are cheap and the selection
    matmul trick buys nothing.
    """
    B = jnp.take(A, parents, axis=1) * jnp.take(X, vars_, axis=1)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    return jnp.matmul(Af.T, Bf, precision=_HIGHEST), jnp.matmul(Bf.T, Bf, precision=_HIGHEST)


def border_columns_ref(A, X, parents, vars_):
    """Candidate columns by direct gather (semantic ground truth)."""
    return jnp.take(A, parents, axis=1) * jnp.take(X, vars_, axis=1)


def gram_accumulate_ref(A, X, parents, vars_, ql0, c0, *, bm: int):
    """Blocked carry-in Gram reduction — the jnp mirror of the Pallas grid
    accumulation (``gram_update_acc``): per ``bm``-row block compute both
    Grams, then fold the blocks into ``(ql0, c0)`` strictly left to right.

    This sequence of fp32 adds makes the reduction *streamable*: accumulating
    row chunks one call at a time (any chunk size that is a multiple of
    ``bm``, zero rows appended at the end are bitwise no-ops) produces the
    identical bits as one call over the concatenated rows.  The per-block
    Grams run as one batched matmul, which matches the per-block 2D matmul
    bit for bit on every backend we test (the same batched-matmul stability
    the class-batched fit relies on), so this reference and the Pallas kernel
    agree exactly at matched ``bm``.

    ``A.shape[0]`` must be a multiple of ``bm`` (ops.py pads with zero rows;
    every value in the OAVI domain is >= +0.0, so zero-block adds cannot even
    flip a signed zero).
    """
    m = A.shape[0]
    nb = m // bm
    B = jnp.take(A, parents, axis=1) * jnp.take(X, vars_, axis=1)
    Af = A.astype(jnp.float32).reshape(nb, bm, A.shape[1])
    Bf = B.astype(jnp.float32).reshape(nb, bm, B.shape[1])
    QLb = jnp.einsum("bmi,bmj->bij", Af, Bf, precision=_HIGHEST)
    Cb = jnp.einsum("bmi,bmj->bij", Bf, Bf, precision=_HIGHEST)

    def body(carry, blocks):
        ql, c = carry
        gql, gc = blocks
        return (ql + gql, c + gc), None

    (ql, c), _ = jax.lax.scan(body, (ql0, c0), (QLb, Cb))
    return ql, c


def squared_hinge_grad_ref(W, b, X, Y):
    """``(gW, gb)``: gradient of the mean squared hinge of the linear SVM
    (``core/svm.py``) at ``(W, b)``.  ``X`` is ``(m, p)``, ``Y`` is ``(m, k)``
    with entries in {-1, +1}; the mean is over the ``m`` rows."""
    m = X.shape[0]
    scores = jnp.matmul(X, W, precision=_HIGHEST) + b  # (m, k)
    margin = 1.0 - Y * scores
    active = jnp.maximum(margin, 0.0)
    g_scores = (-2.0 / m) * (active * Y)  # (m, k)
    gW = jnp.matmul(X.T, g_scores, precision=_HIGHEST)
    gb = jnp.sum(g_scores, axis=0)
    return gW, gb


def ihb_update_ref(N, q, btb, ell, u=None):
    """Theorem 4.9 block-inverse update on the padded inverse (identity in
    the inactive block) — mirrors :func:`repro.core.ihb.append_column`.
    ``u = N q`` is formed here unless the caller already has it.

    Contract (what every in-algorithm caller satisfies): ``q`` is zero at
    slot ``ell`` and beyond (A has no active columns there) and row/col
    ``ell`` of ``N`` is its identity row, so ``u[ell] = q[ell] = 0``.  Under
    that contract the row/col write below is bit-identical to the masked
    formulation ``P*keep*keepᵀ + onehot⊗n2 + n2⊗onehot + (1/s)onehot⊗onehot``
    (kept entries are multiplied by exactly 1.0) while replacing four O(L^2)
    elementwise passes with two O(L) ``dynamic_update_slice`` writes — the
    candidate loop of the (class-batched) degree step runs this once per
    candidate, so the constant matters.

    Two vmap-bit-stability points the class-batched fit relies on: the Schur
    complement reduces via ``sum(q * u)`` rather than a fused dot (matching
    the Pallas kernel), and every remaining op is elementwise, a matvec, or
    a dus — all of which produce identical bits batched and per-instance.
    """
    dtype = N.dtype
    L = N.shape[0]
    onehot = (jnp.arange(L) == ell).astype(dtype)
    keep = 1.0 - onehot
    if u is None:
        u = jnp.matmul(N, q, precision=_HIGHEST)
    s = jnp.maximum(btb - jnp.sum(q * u), jnp.asarray(1e-30, dtype))
    n2 = -u / s
    P = N + jnp.outer(u, u) / s
    colrow = n2 * keep + onehot / s  # new row & column ell (diag = 1/s)
    P = jax.lax.dynamic_update_slice(P, colrow[:, None], (0, ell))
    return jax.lax.dynamic_update_slice(P, colrow[None, :], (ell, 0))


def attention_ref(q, k, v, *, causal=True, q_heads_per_kv=1):
    """Dense softmax attention oracle.

    q: (BHq, Sq, d); k, v: (BHkv, Sk, d) with BHq = BHkv * q_heads_per_kv.
    """
    BHq, Sq, d = q.shape
    BHkv, Sk, _ = k.shape
    if q_heads_per_kv != 1:
        k = jnp.repeat(k, q_heads_per_kv, axis=0)
        v = jnp.repeat(v, q_heads_per_kv, axis=0)
    scale = 1.0 / (d**0.5)
    s = jnp.einsum("hqd,hkd->hqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v).astype(q.dtype)
