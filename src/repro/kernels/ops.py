"""Jit'd public wrappers around the Pallas kernels.

Each op pads its inputs to kernel-aligned shapes and runs one of three
implementations: the Pallas kernel (``use_pallas=True``), the same kernel in
interpreter mode (``interpret=True``, tests), or the pure-jnp reference
(``use_pallas=False``).  ``use_pallas=None`` picks the kernel exactly when
JAX's default backend is a TPU and the reference otherwise (CPU, and the
dry-run's fake CPU devices); an error from the backend query propagates.
Both implementations have the same semantics — ``ref.py`` *is* the spec.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from . import svm_grad as _svm_grad
from .flash_attention import flash_attention as _flash_kernel
from .gram_update import gram_update as _gram_kernel
from .gram_update import gram_update_acc as _gram_acc_kernel
from .ihb_update import ihb_update as _ihb_kernel

# Row-block granularity of the canonical (streamable) Gram reduction: the
# degree step and the out-of-core chunk accumulator both reduce in GRAM_BLOCK
# row blocks, so a streamed fit is bit-identical to the in-memory fit for any
# chunk size that is a multiple of this.
GRAM_BLOCK = 256


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def selection_matrices(parents, vars_, L: int, n: int, dtype=jnp.float32):
    """One-hot (L, K) / (n, K) selectors for gather-as-matmul (gram kernel)."""
    parents = jnp.asarray(parents)
    vars_ = jnp.asarray(vars_)
    K = parents.shape[0]
    Psel = (parents[None, :] == jnp.arange(L)[:, None]).astype(dtype)
    Vsel = (vars_[None, :] == jnp.arange(n)[:, None]).astype(dtype)
    return Psel, Vsel


def gram_update(A, X, parents, vars_, *, bm: int = 512, use_pallas=None, interpret=False):
    """``(QL, C) = (A^T B, B^T B)`` with ``B = A[:, parents] * X[:, vars]``.

    Un-normalized (caller divides by m).  Pads m to a multiple of ``bm``.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not (use_pallas or interpret):
        # off-TPU the one-hot-selection matmul is pure overhead: gather the
        # columns directly (bit-identical — see ref.gram_update_gather_ref)
        return ref.gram_update_gather_ref(A, X, parents, vars_)
    L, n = A.shape[1], X.shape[1]
    Psel, Vsel = selection_matrices(parents, vars_, L, n, A.dtype)
    m = A.shape[0]
    m_pad = _round_up(m, bm)
    if m_pad != m:
        A = jnp.pad(A, ((0, m_pad - m), (0, 0)))
        X = jnp.pad(X, ((0, m_pad - m), (0, 0)))
    return _gram_kernel(A, X, Psel, Vsel, bm=min(bm, m_pad), interpret=interpret)


def gram_accumulate(
    A, X, parents, vars_, acc=None, *, bm: int = GRAM_BLOCK, use_pallas=None,
    interpret=False,
):
    """Canonical blocked Gram reduction with carry: ``(acc_QL + A^T B,
    acc_C + B^T B)`` accumulated sequentially over ``bm``-row blocks.

    This is the degree step's Gram op.  Unlike :func:`gram_update` (whose
    off-TPU fallback is one un-blocked matmul, kept for bit-compat with the
    pre-streaming formulation), the reduction order here is *defined*: fp32
    block partials folded strictly left to right, matching the Pallas grid
    accumulation bit for bit.  That makes it streamable — the out-of-core fit
    feeds row chunks through the same op one at a time (carrying ``acc``) and
    lands on the identical bits as the in-memory fit's single call.

    ``acc=None`` starts from zeros.  ``m`` is padded up to a multiple of
    ``bm`` with zero rows (bitwise no-ops: the OAVI domain is >= +0.0).
    Un-normalized; the caller divides by m.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    L, n = A.shape[1], X.shape[1]
    K = parents.shape[0]
    if acc is None:
        acc = (jnp.zeros((L, K), jnp.float32), jnp.zeros((K, K), jnp.float32))
    m = A.shape[0]
    m_pad = _round_up(m, bm)
    if m_pad != m:
        A = jnp.pad(A, ((0, m_pad - m), (0, 0)))
        X = jnp.pad(X, ((0, m_pad - m), (0, 0)))
    if not (use_pallas or interpret):
        return ref.gram_accumulate_ref(A, X, parents, vars_, acc[0], acc[1], bm=bm)
    Psel, Vsel = selection_matrices(parents, vars_, L, n, A.dtype)
    return _gram_acc_kernel(A, X, Psel, Vsel, acc[0], acc[1], bm=bm, interpret=interpret)


def ihb_update(N, q, btb, ell, *, u=None, use_pallas=None, interpret=False):
    """Theorem 4.9 padded block-inverse update.  ``u = N q`` is formed here
    unless the caller passes it (the degree step already has it from the IHB
    warm start)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not (use_pallas or interpret):
        return ref.ihb_update_ref(N, q, btb, ell, u=u)
    if u is None:
        u = jnp.matmul(N, q, precision=jax.lax.Precision.HIGHEST)
    # Schur complement, reduced exactly as the reference reduces it
    s = jnp.maximum(btb - jnp.sum(q * u), jnp.asarray(1e-30, N.dtype))
    return _ihb_kernel(N, u, s, jnp.asarray(ell, jnp.int32), interpret=interpret)


def svm_grad_picks_kernel(p: int, k: int, dtype) -> bool:
    """What ``use_pallas=None`` means in :func:`svm_grad_operands` and
    :func:`svm_grad` for ``p`` features and ``k`` classes: the kernel on a
    TPU, for f32 data whose accumulators fit the kernel's VMEM."""
    return _on_tpu() and jnp.dtype(dtype) == jnp.float32 and _svm_grad.fits(p, k)


def svm_grad_operands(X, Y, *, use_pallas=None, interpret=False):
    """``(X, Y)`` as :func:`svm_grad` takes them, built once per fit: the
    ``(m, p)`` features and ``(m, k)`` labels as they are for the reference;
    for the kernel a feature-major copy, rows padded with label 0."""
    if use_pallas is None:
        use_pallas = svm_grad_picks_kernel(X.shape[1], Y.shape[1], X.dtype)
    if not (use_pallas or interpret):
        return X, Y
    return _svm_grad.layout(X, Y)


def svm_grad(W, b, X, Y, m: int, *, use_pallas=None, interpret=False):
    """``(gW, gb)``: gradient of the linear SVM's mean squared hinge over
    ``m`` rows at ``(W, b)``; ``X``, ``Y`` from :func:`svm_grad_operands`
    with the same ``use_pallas`` and ``interpret``."""
    if use_pallas is None:
        use_pallas = svm_grad_picks_kernel(W.shape[0], W.shape[1], W.dtype)
    if not (use_pallas or interpret):
        return ref.squared_hinge_grad_ref(W, b, X, Y)
    return _svm_grad.svm_grad(W, b, X, Y, m=m, interpret=interpret)


def multihead_attention(
    q, k, v, *, causal=True, bq=512, bk=512, use_pallas=None, interpret=False
):
    """Flash attention over (B, Hq, S, d) / (B, Hkv, S, d) tensors (GQA-aware).

    Pads S to block multiples.  Padding keys are masked out by causality for
    causal=True; for non-causal we mask via an explicit -inf pad on scores in
    the reference path and rely on zero-padded V rows contributing ~0 weight
    otherwise, so non-causal padded shapes route to the reference.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    B, Hq, Sq, d = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # may differ from d (MLA)
    group = Hq // Hkv
    qf = q.reshape(B * Hq, Sq, d)
    kf = k.reshape(B * Hkv, Sk, d)
    vf = v.reshape(B * Hkv, Sk, dv)
    pad_q = _round_up(Sq, bq) - Sq
    pad_k = _round_up(Sk, bk) - Sk
    padded = pad_q > 0 or pad_k > 0
    if not (use_pallas or interpret) or (padded and not causal):
        out = ref.attention_ref(qf, kf, vf, causal=causal, q_heads_per_kv=group)
        return out.reshape(B, Hq, Sq, dv)
    if padded:
        # causal: padded (future) keys are masked by the causal test; padded
        # query rows produce garbage rows that are sliced off below.
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    out = _flash_kernel(
        qf, kf, vf,
        causal=causal, q_heads_per_kv=group,
        bq=min(bq, qf.shape[1]), bk=min(bk, kf.shape[1]),
        interpret=interpret,
    )
    return out[:, :Sq].reshape(B, Hq, Sq, dv)
