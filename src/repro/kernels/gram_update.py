"""Fused border-evaluation + Gram kernel — OAVI's only O(m) hot spot.

Per degree, OAVI needs (Section 4 / our degree-batched formulation):

    B  = A[:, parents] * X[:, vars]        candidate columns     (m, K)
    QL = A^T B / m                         cross-Gram            (L, K)
    C  = B^T B / m                         candidate Gram        (K, K)

TPU adaptation (DESIGN.md §3): the column *gather* is re-expressed as a
matmul with one-hot selection matrices ``Psel (L, K)`` and ``Vsel (n, K)`` —
gathers are VPU-hostile on TPU while (bm, L) x (L, K) matmuls run on the
MXU.  The kernel streams A and X through VMEM in ``bm``-row blocks and
accumulates both Gram products in fp32 VMEM scratch across the grid:

    grid = (m / bm,)
    per step:  Ab (bm, L), Xb (bm, n)  ->  Bb = (Ab @ Psel) * (Xb @ Vsel)
               QL += Ab^T Bb ;  C += Bb^T Bb

VMEM footprint per step: bm*(L+n+K) + L*K + K*K floats.  With the default
``bm=512``, L=K=256, n<=64: ~0.9 MB streaming + 0.3 MB accumulators — far
under the ~16 MB/core VMEM budget; MXU dims (L, K multiples of 128 by
padding) are hardware-aligned.

``ops.py`` wraps this with padding + the jnp fallback; ``ref.py`` is the
pure-jnp oracle used by the tests (interpret=True comparison).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dot(a, b):
    # full f32 precision: the TPU's default rounds f32 operands to bf16,
    # which moves both Grams (and with them accept/reject decisions)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _accumulate_block(a_ref, x_ref, psel_ref, vsel_ref, ql_ref, c_ref):
    """One m-block: fused select-matmul, product, and Gram accumulation."""
    a = a_ref[...]  # (bm, L)
    # gather-as-matmul: parent columns and variable columns for all K cands
    b = _dot(a, psel_ref[...]) * _dot(x_ref[...], vsel_ref[...])  # (bm, K)
    ql_ref[...] += _dot(a.T, b)
    c_ref[...] += _dot(b.T, b)


def _gram_kernel(a_ref, x_ref, psel_ref, vsel_ref, ql_ref, c_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        ql_ref[...] = jnp.zeros_like(ql_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    _accumulate_block(a_ref, x_ref, psel_ref, vsel_ref, ql_ref, c_ref)


def _gram_acc_kernel(a_ref, x_ref, psel_ref, vsel_ref, ql0_ref, c0_ref, ql_ref, c_ref):
    """Carry-in variant: the accumulators start from ``(ql0, c0)`` instead of
    zero, so a stream of calls over row chunks reduces in exactly the same
    block order as one call over the concatenated rows (out-of-core OAVI)."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        ql_ref[...] = ql0_ref[...]
        c_ref[...] = c0_ref[...]

    _accumulate_block(a_ref, x_ref, psel_ref, vsel_ref, ql_ref, c_ref)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gram_update(
    A: jax.Array,  # (m, L) evaluation matrix (padded columns are zero)
    X: jax.Array,  # (m, n) data
    Psel: jax.Array,  # (L, K) one-hot parent selectors
    Vsel: jax.Array,  # (n, K) one-hot variable selectors
    *,
    bm: int = 512,
    interpret: bool = False,
):
    """Returns ``(QL, C) = (A^T B, B^T B)`` (un-normalized; caller divides by m).

    ``m`` must be a multiple of ``bm`` (ops.py pads; zero rows are harmless
    since they contribute zero to both Gram products).
    """
    m, L = A.shape
    n = X.shape[1]
    K = Psel.shape[1]
    assert m % bm == 0, f"m={m} not a multiple of bm={bm}"
    grid = (m // bm,)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, L), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((L, K), lambda i: (0, 0)),
            pl.BlockSpec((n, K), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, K), lambda i: (0, 0)),
            pl.BlockSpec((K, K), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, K), jnp.float32),
            jax.ShapeDtypeStruct((K, K), jnp.float32),
        ],
        interpret=interpret,
    )(A, X, Psel, Vsel)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gram_update_acc(
    A: jax.Array,  # (m, L) evaluation matrix (padded columns are zero)
    X: jax.Array,  # (m, n) data
    Psel: jax.Array,  # (L, K) one-hot parent selectors
    Vsel: jax.Array,  # (n, K) one-hot variable selectors
    ql0: jax.Array,  # (L, K) fp32 carry-in cross-Gram accumulator
    c0: jax.Array,  # (K, K) fp32 carry-in candidate-Gram accumulator
    *,
    bm: int = 512,
    interpret: bool = False,
):
    """``(ql0 + A^T B, c0 + B^T B)`` accumulated sequentially over ``bm``-row
    blocks — the streamable carry-in form of :func:`gram_update`: feeding row
    chunks (each a multiple of ``bm``) through this kernel one at a time is
    bit-identical to one call over all rows.
    """
    m, L = A.shape
    n = X.shape[1]
    K = Psel.shape[1]
    assert m % bm == 0, f"m={m} not a multiple of bm={bm}"
    grid = (m // bm,)
    return pl.pallas_call(
        _gram_acc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, L), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((L, K), lambda i: (0, 0)),
            pl.BlockSpec((n, K), lambda i: (0, 0)),
            pl.BlockSpec((L, K), lambda i: (0, 0)),
            pl.BlockSpec((K, K), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, K), lambda i: (0, 0)),
            pl.BlockSpec((K, K), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, K), jnp.float32),
            jax.ShapeDtypeStruct((K, K), jnp.float32),
        ],
        interpret=interpret,
    )(A, X, Psel, Vsel, ql0, c0)
