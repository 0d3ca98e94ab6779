"""Fused border-evaluation + Gram kernel — OAVI's only O(m) hot spot.

Per degree, OAVI needs (Section 4 / our degree-batched formulation):

    B  = A[:, parents] * X[:, vars]        candidate columns     (m, K)
    QL = A^T B / m                         cross-Gram            (L, K)
    C  = B^T B / m                         candidate Gram        (K, K)

TPU adaptation (DESIGN.md §3): the column *gather* is re-expressed as a
matmul with one-hot selection matrices ``Psel (L, K)`` and ``Vsel (n, K)`` —
gathers are VPU-hostile on TPU while (bm, L) x (L, K) matmuls run on the
MXU.  The kernel streams A and X through VMEM in ``bm``-row blocks and
accumulates both Gram products in fp32 across the grid.

The grid is tiled over the outputs: ``(K / tk, max(L / tl, K / tk), m / bm)``.
Step ``(j, i, r)`` forms the candidate columns of column tile ``j`` from
row block ``r`` and adds their products into the ``(tl, tk)`` tile
``(i, j)`` of ``QL`` and the ``(tk, tk)`` tile ``(i, j)`` of ``C``:

    Bj = (Ab @ Psel[:, j]) * (Xb @ Vsel[:, j])             (bm, tk)
    QL[i, j] += Ab[:, i]^T Bj ;  C[i, j] += Bi^T Bj

so only a tile of each selector and accumulator is resident in VMEM, and
the row blocks stay the innermost (reduction) axis, folded strictly left to
right into every tile.  Where ``L / tl`` and ``K / tk`` differ, the shorter
output's row-tile index stays on its last tile for the remaining ``i``: the
tile stays resident, untouched, and is written back once.

The tiles come from :func:`tiles`: one tile (``tl = L``, ``tk = K``, and
a grid of the row blocks alone, ``(m / bm,)``) whenever everything fits
the VMEM plan — every shape of a narrow fit — and otherwise the largest
square tiles that fit.  A wide border (K = 2048 at n = 57) no longer needs whole ``(L, K)`` and ``(K, K)``
blocks in VMEM.  Each extra row tile forms its ``Bj`` (and each ``C`` tile
its ``Bi``) again, so larger tiles do less gather work.

``ops.py`` wraps this with padding + the jnp fallback; ``ref.py`` is the
pure-jnp oracle used by the tests (interpret=True comparison).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM a kernel may use: raised from Mosaic's default scoped 16 MiB, within
# the 128 MiB of a TPU v5e core.  The plan (:func:`vmem_bytes`) keeps its
# blocks inside _VMEM_BUDGET, leaving the rest for Mosaic's temporaries.
_VMEM_LIMIT = 64 << 20
_VMEM_BUDGET = 40 << 20
_MIN_TILE = 128  # lanes: a tile narrower than its array is a multiple of this


def _dot(a, b):
    # full f32 precision: the TPU's default rounds f32 operands to bf16,
    # which moves both Grams (and with them accept/reject decisions)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _padded_bytes(rows: int, cols: int) -> int:
    """Bytes of an f32 ``(rows, cols)`` block in VMEM's (8, 128) tiling."""
    return 4 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128)


def vmem_bytes(L: int, K: int, n: int, bm: int, tl: int, tk: int) -> int:
    """VMEM the kernel's blocks take at tiles ``(tl, tk)``: each block twice
    (the pipeline's double buffer), plus the step's products."""
    blocks = [(bm, L), (bm, n), (L, tk), (n, tk), (tl, tk), (tk, tk), (tl, tk), (tk, tk)]
    temps = [(bm, tk)] * 3 + [(tl, tk), (tk, tk)]
    if tl < L:
        blocks.append((bm, tl))
    if tk < K:
        blocks += [(L, tk), (n, tk)]
        temps += [(bm, tk)] * 3
    return (2 * sum(_padded_bytes(*b) for b in blocks)
            + sum(_padded_bytes(*t) for t in temps))


def tiles(L: int, K: int, n: int, bm: int) -> tuple[int, int]:
    """``(tl, tk)``: one tile where the plan holds all of ``QL`` and ``C``,
    else the largest square tiles (a power of two times 128 that divides
    the tiled sides) that fit, never below 128."""
    if vmem_bytes(L, K, n, bm, L, K) <= _VMEM_BUDGET:
        return L, K
    best = min(L, _MIN_TILE), min(K, _MIN_TILE)
    t = 2 * _MIN_TILE
    while t < max(L, K):
        tl, tk = min(L, t), min(K, t)
        if L % tl or K % tk or vmem_bytes(L, K, n, bm, tl, tk) > _VMEM_BUDGET:
            break
        best = tl, tk
        t *= 2
    return best


def _gram_acc_kernel(*refs, n_l: int, n_k: int):
    """Step ``(j, i, r)``: candidate columns of tile ``j`` from row block
    ``r``, added into tile ``(i, j)`` of both accumulators, which start
    from their carry-in ``(ql0, c0)`` at the first row block — so a stream
    of calls over row chunks reduces in exactly the same block order as
    one call over the concatenated rows (out-of-core OAVI)."""
    a_ref, x_ref, psel_ref, vsel_ref, *rest = refs
    at_ref = rest.pop(0) if n_l > 1 else None
    pseli_ref, vseli_ref = (rest.pop(0), rest.pop(0)) if n_k > 1 else (None, None)
    ql0_ref, c0_ref, ql_ref, c_ref = rest
    n_i = max(n_l, n_k)
    if n_i > 1:
        i, first = pl.program_id(1), pl.program_id(2) == 0
    else:
        i, first = 0, pl.program_id(0) == 0

    def live(n_rows, body):
        # past an output's last row tile its block stays resident, untouched
        if n_rows == n_i:
            body()
        else:
            pl.when(i < n_rows)(body)

    def carry_in(acc_ref, acc0_ref):
        def body():
            acc_ref[...] = acc0_ref[...]
        return body

    # both carry-ins in one region ahead of the block's products: placed
    # between the products, the branch cost a one-tile Appendix C grid 21 %
    @pl.when(first)
    def _init():
        live(n_l, carry_in(ql_ref, ql0_ref))
        live(n_k, carry_in(c_ref, c0_ref))

    a = a_ref[...]  # (bm, L)
    # gather-as-matmul: parent columns and variable columns of tile j
    b = _dot(a, psel_ref[...]) * _dot(x_ref[...], vsel_ref[...])  # (bm, tk)

    def cross():
        left = a if at_ref is None else at_ref[...]
        ql_ref[...] += _dot(left.T, b)

    def candidates():
        bi = b if pseli_ref is None else _dot(a, pseli_ref[...]) * _dot(x_ref[...], vseli_ref[...])
        c_ref[...] += _dot(bi.T, b)

    live(n_l, cross)
    live(n_k, candidates)


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
    blocks, on the output tiles of :func:`tiles`: feeding row chunks (each a
    multiple of ``bm``) through this kernel one at a time is bit-identical
    to one call over all rows.
    """
    m, L = A.shape
    n = X.shape[1]
    K = Psel.shape[1]
    tl, tk = tiles(L, K, n, bm)
    assert m % bm == 0, f"m={m} not a multiple of bm={bm}"
    n_l, n_k = L // tl, K // tk
    n_i = max(n_l, n_k)
    tiled = n_i > 1

    def row_tile(n_rows):
        # the shorter output stays on its last row tile for the remaining i
        if n_rows == n_i:
            return lambda i: i
        return lambda i: jnp.minimum(i, n_rows - 1)

    tile_l, tile_k = row_tile(n_l), row_tile(n_k)

    def spec(shape, index):
        # one tile: a grid of the row blocks alone
        return pl.BlockSpec(shape, index if tiled else (lambda r: index(0, 0, r)))

    in_specs = [
        spec((bm, L), lambda j, i, r: (r, 0)),
        spec((bm, n), lambda j, i, r: (r, 0)),
        spec((L, tk), lambda j, i, r: (0, j)),
        spec((n, tk), lambda j, i, r: (0, j)),
    ]
    operands = [A, X, Psel, Vsel]
    if n_l > 1:  # A's columns of row tile i
        in_specs.append(spec((bm, tl), lambda j, i, r: (r, tile_l(i))))
        operands.append(A)
    if n_k > 1:  # selectors of the candidates of row tile i
        in_specs += [
            spec((L, tk), lambda j, i, r: (0, tile_k(i))),
            spec((n, tk), lambda j, i, r: (0, tile_k(i))),
        ]
        operands += [Psel, Vsel]
    ql_spec = spec((tl, tk), lambda j, i, r: (tile_l(i), j))
    c_spec = spec((tk, tk), lambda j, i, r: (tile_k(i), j))
    return pl.pallas_call(
        functools.partial(_gram_acc_kernel, n_l=n_l, n_k=n_k),
        grid=(n_k, n_i, m // bm) if tiled else (m // bm,),
        in_specs=in_specs + [ql_spec, c_spec],
        out_specs=[ql_spec, c_spec],
        out_shape=[
            jax.ShapeDtypeStruct((L, K), jnp.float32),
            jax.ShapeDtypeStruct((K, K), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands, ql0, c0)


def gram_update(A, X, Psel, Vsel, *, bm: int = 512, interpret: bool = False):
    """Returns ``(QL, C) = (A^T B, B^T B)`` (un-normalized; caller divides by
    m): :func:`gram_update_acc` from zero accumulators.

    ``m`` must be a multiple of ``bm`` (ops.py pads; zero rows are harmless
    since they contribute zero to both Gram products).
    """
    L, K = Psel.shape
    zeros = (jnp.zeros((L, K), jnp.float32), jnp.zeros((K, K), jnp.float32))
    return gram_update_acc(A, X, Psel, Vsel, *zeros, bm=bm, interpret=interpret)
