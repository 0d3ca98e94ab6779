"""Squared-hinge gradient of the linear SVM in one pass over the features.

FISTA (``core/svm.py``) needs, at every iteration, the gradient of the mean
squared hinge at ``(W, b)`` over the ``m`` training rows:

    s  = X W + b                          scores           (m, k)
    g  = (-2/m) max(0, 1 - Y * s) * Y     score gradient   (m, k)
    gW = X^T g,   gb = sum over rows of g                  (p, k), (k,)

``X`` never changes inside the loop, yet XLA runs this as three passes in
series over it (a copy into VMEM, ``X W``, ``X^T g``).  This kernel streams
row blocks of ``X`` through VMEM once per call and does all three steps on
each block while the next one is fetched.

Layout (built once per fit, see :func:`layout`): ``X`` is feature-major,
``(p, R, 128)``, so feature ``j``'s rows form a lane-dense ``(R, 128)`` slab
and no feature count pads (a ``(p, m)`` array would pad p = 12 sublanes to
16); ``Y`` is ``(k, R, 128)``.  ``R`` rounds ``m / 128`` up to whole grid
blocks (:func:`block_rows`); the rows past ``m`` hold zero features and
label 0, so their ``g`` is exactly 0 and they add nothing to ``gW`` or
``gb``.

With k (classes) output columns the MXU would run almost empty, so the
products are f32 multiplies and adds on the VPU, ``STEP_ROWS`` rows of 128
(two vregs a feature) at a time, eight features a loop trip:
``s_c = sum_j W[j, c] x_j`` with ``W`` and ``b`` as SMEM scalars, then
``g_c``, then ``acc[j, c] += x_j g_c`` and ``acc[b, c] += g_c`` into
``(8, 128)`` f32 accumulators that stay in VMEM across the grid.  The last
grid step sums each accumulator.  Everything is f32; only the order of the
sums differs from the jnp reference
:func:`repro.kernels.ref.squared_hinge_grad_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8  # f32 rows of 128 in one vreg
STEP_ROWS = 16  # rows of 128 in a step of the inner loop: two vregs a feature
# VMEM the kernel plans for: its accumulators plus double-buffered blocks of
# X and Y, inside the 16 MiB that Mosaic allows a kernel by default.
_VMEM_BUDGET = 12 << 20
_F32 = 4
_GROUP = 8  # features a trip of the inner loops


def _over_features(p: int, body, carry):
    """``carry = body(j0, n, carry)`` over groups of ``n`` features from
    ``j0``: ``_GROUP`` a loop trip (Mosaic unrolls a loop wholly or not at
    all), then the rest as one group."""
    carry = jax.lax.fori_loop(
        0, p // _GROUP, lambda q, carry: body(q * _GROUP, _GROUP, carry), carry
    )
    if p % _GROUP:
        carry = body(p - p % _GROUP, p % _GROUP, carry)
    return carry


def _tree_sum(terms):
    while len(terms) > 1:
        pairs = [terms[i] + terms[i + 1] for i in range(0, len(terms) - 1, 2)]
        terms = pairs + terms[len(terms) - len(terms) % 2 :]
    return terms[0]


def _fold(v):
    """``(STEP_ROWS, 128)`` -> ``(8, 128)``: the sum of its vregs."""
    return _tree_sum([v[i : i + SUBLANES] for i in range(0, v.shape[0], SUBLANES)])


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _acc_bytes(p: int, k: int) -> int:
    return (p + 1) * k * SUBLANES * LANES * _F32  # gW and gb accumulators


def _max_block_rows(p: int, k: int) -> int:
    """Most rows of 128 a grid step may hold, a multiple of ``STEP_ROWS``; 0
    where the accumulators and the smallest double-buffered block do not fit."""
    per_row = 2 * (p + k) * LANES * _F32
    most = (_VMEM_BUDGET - _acc_bytes(p, k)) // per_row
    return most // STEP_ROWS * STEP_ROWS


def fits(p: int, k: int) -> bool:
    """Whether the kernel holds ``p`` features and ``k`` classes in VMEM."""
    return _max_block_rows(p, k) >= STEP_ROWS


def block_rows(p: int, k: int, m: int) -> tuple[int, int]:
    """``(br, R)``: rows of 128 per grid step and in all, ``R`` a multiple of
    ``br`` covering ``m``.  Of the block counts from the fewest that fit to
    twice that, the one that pads the fewest rows."""
    units = _cdiv(_cdiv(m, LANES), STEP_ROWS)
    fewest = _cdiv(units, _max_block_rows(p, k) // STEP_ROWS)
    nb = min(range(fewest, 2 * fewest + 1), key=lambda nb: (_cdiv(units, nb) * nb, nb))
    br = _cdiv(units, nb) * STEP_ROWS
    return br, br * nb


@jax.jit
def layout(X: jax.Array, Y: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(m, p)`` features and ``(m, k)`` labels as the kernel reads them:
    ``(p, R, 128)`` and ``(k, R, 128)``, rows past ``m`` zero."""
    m, p = X.shape
    k = Y.shape[1]
    _, R = block_rows(p, k, m)
    pad = ((0, R * LANES - m), (0, 0))

    def feature_major(A):
        return jnp.pad(A, pad).T.reshape(A.shape[1], R, LANES)

    return feature_major(X), feature_major(Y)


def _svm_grad_kernel(w_ref, b_ref, x_ref, y_ref, out_ref, acc_ref, *, scale):
    p, br, _ = x_ref.shape
    k = y_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(r, carry):
        rows = pl.ds(pl.multiple_of(r * STEP_ROWS, STEP_ROWS), STEP_ROWS)

        def scores(j0, n, s):
            x = x_ref[pl.ds(j0, n), rows, :]
            return tuple(
                s[c] + _tree_sum([w_ref[(j0 + u) * k + c] * x[u] for u in range(n)])
                for c in range(k)
            )

        zero = jnp.zeros((STEP_ROWS, LANES), jnp.float32)
        s = _over_features(p, scores, (zero,) * k)
        g = []
        for c in range(k):
            y = y_ref[c, rows, :]
            active = jnp.maximum(1.0 - y * (s[c] + b_ref[c]), 0.0)
            g.append(scale * (active * y))
            acc_ref[p * k + c] += _fold(g[c])

        def gradient(j0, n, carry):
            x = x_ref[pl.ds(j0, n), rows, :]
            at = pl.ds(j0 * k, n * k)
            acc_ref[at] += jnp.stack([_fold(x[u] * g[c]) for u in range(n) for c in range(k)])
            return carry

        return _over_features(p, gradient, carry)

    jax.lax.fori_loop(0, br // STEP_ROWS, step, 0)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _finish():
        out_ref[...] = jnp.sum(jnp.sum(acc_ref[...], axis=1), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def svm_grad(
    W: jax.Array,  # (p, k) f32
    b: jax.Array,  # (k,) f32
    X: jax.Array,  # (p, R, 128) f32, from layout()
    Y: jax.Array,  # (k, R, 128) f32, from layout()
    *,
    m: int,  # real rows: the mean divides by it
    interpret: bool = False,
):
    """``(gW, gb)`` of the mean squared hinge over the ``m`` real rows."""
    p, R, _ = X.shape
    k = Y.shape[0]
    br, _ = block_rows(p, k, m)
    assert R % br == 0, f"R={R} rows of 128 not a multiple of the block {br}"
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_svm_grad_kernel, scale=-2.0 / m),
        grid=(R // br,),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((p, br, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((k, br, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec(((p + 1) * k, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(((p + 1) * k, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM(((p + 1) * k, SUBLANES, LANES), jnp.float32)],
        interpret=interpret,
        name="svm_grad",
    )(W.reshape(p * k), b, X, Y)
    return out[: p * k].reshape(p, k), out[p * k :, 0]
