"""Data-parallel OAVI via ``shard_map`` — the paper's technique at pod scale.

The degree-batched Gram formulation of :mod:`repro.core.oavi` is the unit of
distribution.  With the sample axis ``m`` sharded over the mesh's data axes:

* step (1) — candidate-column construction ``B = A[:, parents] * X[:, vars]``
  is purely local (elementwise on the local shard),
* step (2) — the two Gram products run through the fused
  :func:`repro.kernels.ops.gram_update` kernel on each device's local shard
  (Pallas on TPU, the bit-identical jnp fallback elsewhere), followed by a
  ``psum`` over the data axes.  These psums are the *only* collectives:
  O(L*K + K*K) floats per degree, independent of m.
* step (3) — the sequential acceptance loop runs on the replicated Gram
  blocks, bit-identically on every device; appended columns are written back
  into the *local* shard of A.

Weak scaling is therefore exact: per-device FLOPs are O((m/devices) * L * K)
and collective bytes are m-independent — the distributed embodiment of the
paper's "linear in m" claim (Theorem 4.3 keeps L bounded).

Capacity growth and compiles follow :mod:`repro.core.oavi`: pow2 ``(Lcap,
Kcap)`` buckets, device-side regrowth, and a global cache of the jitted
sharded step keyed by ``(config, mesh, data_axes)`` — ``stats["recompiles"]``
counts the compiles a fit actually triggered.

Padding: ``m`` is padded up to a multiple of the number of data shards; the
constant-1 column is built as the *sample mask*, so padded rows are exactly
zero in every column of A (every term column is a product of the mask column
with data columns) and contribute nothing to any Gram quantity.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import ihb as ihb_mod
from . import terms as terms_mod
from .. import obs
from .oavi import (
    FitScope,
    Generator,
    OAVIConfig,
    OAVIModel,
    _make_degree_step,
    border_index_arrays,
    collect_degree,
    degree_step_entry,
    init_fit_stats,
    pow2_bucket,
)
from .ordering import pearson_order


def data_spec(data_axes: Sequence[str]) -> P:
    """PartitionSpec sharding the leading (sample/row) axis over ``data_axes``."""
    axes = tuple(data_axes)
    return P(axes if len(axes) > 1 else axes[0], None)


def class_data_spec(data_axes: Sequence[str]) -> P:
    """PartitionSpec for class-batched ``(k, m, ...)`` buffers: class axis
    replicated, sample axis sharded over ``data_axes``."""
    axes = tuple(data_axes)
    return P(None, axes if len(axes) > 1 else axes[0], None)


def num_data_shards(mesh: Mesh, data_axes: Sequence[str]) -> int:
    """Total device count along the mesh's data axes."""
    return int(np.prod([mesh.shape[a] for a in data_axes]))


def _emit_shard_event(name, shard) -> None:
    """Host half of the per-shard probe (``jax.debug.callback`` target)."""
    obs.event(str(name), shard=int(shard))


def shard_probe(step, mesh: Mesh, axes: Sequence[str], name: str):
    """Compile a per-shard instant-event probe into a shard_map'ed step.

    ``jax.debug.callback`` is an effect-only op — it changes no numerics and
    costs one host callback per shard per dispatch — so the probe lives in
    the cached compiled step unconditionally (the degree-step cache key is
    unchanged) and the *recording* is gated at runtime by
    :func:`repro.obs.enabled` inside ``obs.event``.  The emitted
    ``fit/shard_step`` instants are the per-shard visibility the PR 8 span
    work could not reach from host-side spans: one marker per device per
    degree step, labeled with the flat shard index.
    """
    sizes = [int(mesh.shape[a]) for a in axes]

    def probed(*args):
        idx = jnp.int32(0)
        for a, size in zip(axes, sizes):
            idx = idx * jnp.int32(size) + jax.lax.axis_index(a)
        jax.debug.callback(_emit_shard_event, name, idx)
        return step(*args)

    return probed


def make_sharded_degree_step(
    cfg: OAVIConfig, mesh: Mesh, data_axes: Sequence[str] = ("data",)
):
    """Jitted shard_map-wrapped degree step: Gram psums over ``data_axes``."""
    axes = tuple(data_axes)
    reduce_fn = lambda x: jax.lax.psum(x, axes)  # noqa: E731
    step = _make_degree_step(cfg, reduce_fn=reduce_fn)
    step = shard_probe(step, mesh, axes, "fit/shard_step")
    dspec = data_spec(axes)
    rep = P()

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(dspec, dspec, rep, rep, rep, rep, rep, rep),
        out_specs=(dspec, rep),
        check_vma=False,
    )
    return jax.jit(sharded)


def make_class_batched_sharded_degree_step(
    cfg: OAVIConfig, mesh: Mesh, data_axes: Sequence[str] = ("data",),
):
    """Class-batched AND data-sharded degree step: the class axis (``vmap``)
    composed with the sample-sharded psum path.

    Layout: ``A``/``X`` are ``(k, m_cap, ·)`` with the class axis replicated
    and the sample axis sharded over ``data_axes`` — each device holds every
    class's row shard, the vmapped Gram products run on the local shards, and
    one psum per degree (now carrying ``(k, L, K) + (k, K, K)`` floats, still
    m-independent) replicates the blocks.  The candidate loop then replays
    bit-identically on every device for all classes at once.
    """
    axes = tuple(data_axes)
    reduce_fn = lambda x: jax.lax.psum(x, axes)  # noqa: E731
    step = jax.vmap(_make_degree_step(cfg, reduce_fn=reduce_fn))
    # probe outside the vmap, inside the shard_map: one instant per device
    # per dispatch (not per class)
    step = shard_probe(step, mesh, axes, "fit/shard_step")
    bspec = class_data_spec(axes)
    rep = P()

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(bspec, bspec, rep, rep, rep, rep, rep, rep),
        out_specs=(bspec, rep),
        check_vma=False,
    )
    return jax.jit(sharded)


def shard_samples(
    X: np.ndarray, mesh: Mesh, data_axes: Sequence[str] = ("data",), dtype=jnp.float32
) -> Tuple[jax.Array, jax.Array, int]:
    """Pad ``m`` to the data-shard count and place X on the mesh.

    Returns ``(X_sharded, mask_sharded, m_true)``; ``mask`` is 1.0 on real
    rows, 0.0 on padding.
    """
    m, n = X.shape
    shards = num_data_shards(mesh, data_axes)
    m_pad = ((m + shards - 1) // shards) * shards
    Xp = np.zeros((m_pad, n), dtype=np.asarray(X).dtype)
    Xp[:m] = X
    mask = np.zeros((m_pad, 1), dtype=np.float32)
    mask[:m] = 1.0
    dspec = data_spec(data_axes)
    # host -> row shards directly: no device ever holds all rows
    np_dtype = np.dtype(jnp.dtype(dtype))
    xs = jax.device_put(Xp.astype(np_dtype, copy=False), NamedSharding(mesh, dspec))
    ms = jax.device_put(mask.astype(np_dtype, copy=False), NamedSharding(mesh, dspec))
    return xs, ms, m


def fit(
    X,
    config: OAVIConfig = OAVIConfig(),
    *,
    mesh: Mesh,
    data_axes: Sequence[str] = ("data",),
) -> OAVIModel:
    """Distributed OAVI: same semantics as :func:`repro.core.oavi.fit`, with
    the sample axis sharded over ``data_axes`` of ``mesh``."""
    dtype = config.jax_dtype()
    X = np.asarray(X)
    m, n = X.shape
    stats = init_fit_stats(
        m,
        n,
        mesh={a: int(mesh.shape[a]) for a in mesh.axis_names},
        data_axes=list(data_axes),
    )

    with FitScope(stats, backend="sharded") as scope:
        perm = None
        if config.ordering in ("pearson", "reverse_pearson"):
            perm = pearson_order(X, reverse=(config.ordering == "reverse_pearson"))
            X = X[:, perm]

        Xd, mask, m_true = shard_samples(X, mesh, data_axes, dtype)
        m_pad = Xd.shape[0]
        stats["m_padded"] = m_pad
        book = terms_mod.TermBook(n=n)
        generators: List[Generator] = []

        Lcap = pow2_bucket(config.cap_terms)
        dspec = data_spec(data_axes)
        a_shard = NamedSharding(mesh, dspec)
        rep = NamedSharding(mesh, P())
        # constant column = sample mask (zero on padded rows); padding keeps
        # the row sharding of the mask on any mesh axis type
        A = jax.device_put(jnp.pad(mask, ((0, 0), (0, Lcap - 1))), a_shard)
        # normalized convention: AtA[0,0] = ||mask||^2 / m = 1
        state = ihb_mod.init_state(
            Lcap, jnp.asarray(1.0, dtype), dtype, factors=config.ihb_factors()
        )
        state = jax.device_put(state, rep)
        ell = 1

        axes = tuple(data_axes)
        entry = degree_step_entry(
            config,
            backend_key=(mesh, axes),
            jitted_builder=lambda: make_sharded_degree_step(config, mesh, axes),
        )
        m_total = jnp.asarray(float(m_true), dtype)

        d = 0
        while True:
            d += 1
            if d > config.max_degree:
                stats["termination"] = f"max_degree={config.max_degree}"
                break
            border = book.border(d)
            if not border:
                stats["termination"] = "empty_border"
                break
            K = len(border)
            stats["border_sizes"].append(K)
            stats["degrees"].append(d)

            # capacity management: device-side regrowth into the next pow2 bucket
            while ell + K > Lcap:
                A = jax.device_put(jnp.pad(A, ((0, 0), (0, Lcap))), a_shard)
                Lcap *= 2
                scope.regrowth(Lcap)
                state = jax.device_put(ihb_mod.grow_state(state, Lcap), rep)

            Kcap = max(config.cap_border, pow2_bucket(K))
            parents, vars_, valid = border_index_arrays(book, border, Kcap)

            step_args = (
                A,
                Xd,
                state,
                jnp.asarray(ell, jnp.int32),
                jnp.asarray(parents),
                jnp.asarray(vars_),
                jnp.asarray(valid),
                m_total,
            )
            sig = (m_pad, n, Lcap, Kcap, str(dtype))
            scope.note_signature(entry.seen, sig)

            with scope.degree(d, K=K):
                A, st = entry.fn(*step_args)
                state = st.ihb
                accepted = np.asarray(st.accepted)
                mses = np.asarray(st.mses)
                coeffs = np.asarray(st.coeffs)
                iters = np.asarray(st.iters)
            stats["solver_iters"].append(int(iters[:K].sum()))

            ell = collect_degree(book, border, accepted, mses, coeffs, generators)

        scope.finalize(book, generators, Lcap, config)
    return OAVIModel(
        n=n,
        psi=config.psi,
        book=book,
        generators=generators,
        feature_perm=perm,
        stats=stats,
        dtype=config.dtype,
    )
