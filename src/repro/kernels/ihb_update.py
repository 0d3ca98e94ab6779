"""IHB block-inverse update (Theorem 4.9) as a Pallas TPU kernel.

The O(l^2) hot path of Inverse Hessian Boosting: given ``N = (A^T A)^{-1}``
(padded to capacity L with an identity block), ``u = N q`` for the new
column's Gram vector ``q = A^T b``, and the Schur complement
``s = ||b||^2 - q^T u``, produce the updated inverse after appending column
``b`` at slot ``ell``:

    N' = [[N + u u^T / s, -u/s], [-u^T/s, 1/s]]   (written in place at slot ell)

``u`` is an input rather than recomputed here: the degree step already
forms ``N q`` for the IHB warm start, so the kernel is a pure rank-one
update plus one row/column write — elementwise work on the VPU.

The grid runs over ``bl``-row blocks of ``N``, so VMEM holds
``O(bl * L)`` floats per step instead of the whole ``(L, L)`` inverse (which
no longer fits at L = 2048, nor at L = 1024 under a class-batched ``vmap``).
The slot ``ell`` is scalar-prefetched into SMEM; ``s`` rides as a ``(1, 1)``
VMEM block (a float scalar cannot be prefetched, and a ``(1, 1)`` block
stays legal when ``vmap`` adds a batch axis).  Masking with the ``ell``
one-hot keeps the padded identity block intact, exactly like the jnp
reference :func:`repro.kernels.ref.ihb_update_ref`, whose arithmetic the
kernel repeats operation for operation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of one (bl, L) f32 block of N: the input and output blocks are each
# double-buffered, so a step holds about 4x this in VMEM.
_BLOCK_BYTES = 1 << 20


def row_block(L: int) -> int:
    """Rows of ``N`` per grid step: a power of two (``L`` is one), at least
    8 (the f32 sublane tile) and at most ``L``."""
    return max(min(L, _BLOCK_BYTES // (4 * L)), min(L, 8))


def _ihb_kernel(ell_ref, s_ref, n_ref, ucol_ref, urow_ref, out_ref):
    bl, L = n_ref.shape
    ell = ell_ref[0]  # scalar-prefetched into SMEM
    s = s_ref[...]  # (1, 1)
    rows = pl.program_id(0) * bl + jax.lax.broadcasted_iota(jnp.int32, (bl, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    u_col = ucol_ref[...]  # (bl, 1): this block's slice of u
    u_row = urow_ref[...]  # (1, L): all of u
    dt = u_row.dtype
    # new row / column ell (diagonal 1/s), as a column slice and a full row
    c_col = (-u_col / s) * (rows != ell).astype(dt) + (rows == ell).astype(dt) / s
    c_row = (-u_row / s) * (cols != ell).astype(dt) + (cols == ell).astype(dt) / s
    P = n_ref[...] + (u_col * u_row) / s
    out_ref[...] = jnp.where(rows == ell, c_row, jnp.where(cols == ell, c_col, P))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ihb_update(
    N: jax.Array,  # (L, L) current padded inverse
    u: jax.Array,  # (L,) N q (zeros at inactive slots)
    s: jax.Array,  # scalar Schur complement, already floored > 0
    ell: jax.Array,  # scalar int: append slot
    *,
    interpret: bool = False,
) -> jax.Array:
    L = N.shape[0]
    bl = row_block(L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L // bl,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, ell: (0, 0)),
            pl.BlockSpec((bl, L), lambda i, ell: (i, 0)),
            pl.BlockSpec((bl, 1), lambda i, ell: (i, 0)),
            pl.BlockSpec((1, L), lambda i, ell: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bl, L), lambda i, ell: (i, 0)),
    )
    return pl.pallas_call(
        _ihb_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, L), N.dtype),
        interpret=interpret,
    )(
        ell.astype(jnp.int32).reshape(1),
        s.astype(N.dtype).reshape(1, 1),
        N,
        u.reshape(L, 1),
        u.reshape(1, L),
    )
