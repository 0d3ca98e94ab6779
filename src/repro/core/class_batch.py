"""Class-batched OAVI: the k per-class fits of Algorithm 2 as ONE vmapped fit.

The paper's end-to-end classifier fits one generator model per class; the
per-class problems are embarrassingly parallel (they share nothing but the
algorithm), yet a sequential loop pays k full dispatch/sync pipelines per
degree.  This module stacks the k problems into one batched state and drives
them through a single jitted ``vmap`` of the exact same degree step the
sequential path uses (:func:`repro.core.oavi._make_degree_step`):

* **Padded class buckets** — evaluation matrices are padded to a shared pow2
  ``(m_cap, Lcap, Kcap)`` bucket.  Rows: each class's samples are padded to
  ``m_cap = pow2_bucket(max_c m_c)`` with the constant-1 column built as the
  per-class *row mask* (the same convention as the data-sharded path), so
  padded rows are exactly zero in every column of A and contribute nothing
  to any Gram quantity.  Columns: one shared ``Lcap`` / per-degree ``Kcap``
  across classes, regrown when the *largest* class overflows.
* **Batched state** — ``A`` is ``(k, m_cap, Lcap)``, the
  :class:`~repro.core.ihb.IHBState` factors gain a leading class axis
  ``(k, L, L)``, and the per-degree border index arrays are ``(k, Kcap)``.
* **One vmapped degree step** — the Gram products
  (:func:`repro.kernels.ops.gram_update`), the candidate ``fori_loop`` and
  the IHB updates (:func:`repro.kernels.ops.ihb_update`) execute as batched
  kernels: one dispatch per degree instead of k.
* **Per-class done masking** — classes terminate at different degrees; a
  finished class rides along with an all-``False`` validity mask, which makes
  its slice of the step a bitwise no-op (nothing accepted, nothing appended,
  ``ell`` and the IHB factors untouched).
* **Shared degree-step cache** — the jitted ``vmap``'d step lives in the
  global per-``(config, backend)`` cache of :mod:`repro.core.oavi`, keyed by
  ``backend_key='class_batch'`` (plus the mesh for the sharded composition),
  so a warm multi-class refit at the same ``(k, m_cap, Lcap, Kcap)`` bucket
  compiles nothing.

Bit-exactness
-------------
For eligible configs (:func:`repro.core.oavi.class_batchable`: every engine
with the Theorem 4.9 inverse) every primitive in the degree step is
vmap-bit-stable — batched matmuls, matvecs, gathers and scatters produce the
same bits as their per-slice counterparts — so the batched fit is
**bit-exact** against the sequential fit *at matched capacity*: same
``Lcap``/``Kcap`` buckets and same row count.  Classes whose
``m_c == m_cap`` (no row padding — e.g. equal-size class buckets at a pow2
size) therefore reproduce :func:`repro.core.oavi.fit` exactly; padded
classes are bit-exact against the matched-``m_cap`` reference (a ``k=1`` run
of this module) and structure-exact vs the unpadded sequential fit, with
coefficients differing only by the fp summation-order drift of the longer
(zero-extended) Gram reduction.

Oracle / WIHB configs run the convex oracles' ``while_loop`` solvers
(:mod:`repro.core.oracles`) under the same ``vmap``: a batched solve runs
until its slowest lane stops, and stopped lanes carry state as bitwise
no-ops, so the bit-exactness contract above carries over to oracle engines
unchanged.

Distribution composes: with a mesh, the class axis (vmap) nests inside the
data-sharded ``shard_map`` psum path — see
:func:`repro.core.distributed.make_class_batched_sharded_degree_step`.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import ihb as ihb_mod
from .. import obs
from ..kernels import ops as kernel_ops
from . import terms as terms_mod
from .oavi import (
    FitScope,
    Generator,
    OAVIConfig,
    OAVIModel,
    _make_degree_step,
    _np_dtype,
    border_index_arrays,
    class_batchable,
    collect_degree,
    degree_step_entry,
    init_fit_stats,
    pow2_bucket,
)
from .ordering import pearson_order

# Monotonic id per batched fit: lets stats consumers (the classifier's
# aggregation) count each batch's shared recompiles/regrowths exactly once.
_GROUP_IDS = itertools.count()


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@partial(jax.jit, static_argnames=("Lcap", "factors"))
def _init_batch_arrays(mask, Lcap: int, factors):
    """Initial batched fit arrays in ONE cached dispatch: A with the row-mask
    constant column, plus the per-class IHB factors.  Built eagerly this is
    half a dozen scatter/eye dispatches per fit — measurable host overhead in
    the dispatch-bound regime the batched path exists for.  Same ops as the
    eager form, so the values are bit-identical."""
    k = mask.shape[0]
    dtype = mask.dtype
    # padding keeps the mask's sharding: a row-sharded mask gives a
    # row-sharded A, never a whole A on one device
    A = jnp.pad(mask[:, :, None], ((0, 0), (0, 0), (0, Lcap - 1)))
    # normalized Gram convention: AtA[0,0] = ||mask_c||^2 / m_c = 1 per class
    state = ihb_mod.batch_state(
        ihb_mod.init_state(Lcap, jnp.asarray(1.0, dtype), dtype, factors=factors),
        k,
    )
    return A, state


def _batched_entry(config: OAVIConfig, mesh, data_axes):
    """Cached jitted batched step: plain ``jit(vmap(step))`` locally, the
    vmap-inside-shard_map composition when a mesh is given."""
    if mesh is None:
        return degree_step_entry(
            config,
            backend_key="class_batch",
            jitted_builder=lambda: jax.jit(jax.vmap(_make_degree_step(config))),
        )
    from . import distributed as distributed_mod

    axes = tuple(data_axes)
    return degree_step_entry(
        config,
        backend_key=("class_batch", mesh, axes),
        jitted_builder=lambda: distributed_mod.make_class_batched_sharded_degree_step(
            config, mesh, axes
        ),
    )


def fit_classes(
    Xs: Sequence[np.ndarray],
    config: OAVIConfig = OAVIConfig(),
    *,
    mesh=None,
    data_axes: Sequence[str] = ("data",),
    m_cap: Optional[int] = None,
) -> List[OAVIModel]:
    """Fit one OAVI model per class, all classes batched through one vmapped
    degree step.  Same semantics as ``[oavi.fit(X, config) for X in Xs]``
    (bit-exact at matched capacity — see the module docstring).

    ``m_cap`` overrides the shared row bucket (default
    ``pow2_bucket(max_c m_c)``, rounded up to the data-shard count when a
    ``mesh`` is given).  Every returned model's stats carry a
    ``"class_batch"`` dict (``group``/``size``/``index``) whose shared
    ``recompiles``/``regrowths`` must be counted once per group, not once
    per class — see :func:`repro.api.aggregate_fit_stats`.
    """
    if not class_batchable(config):
        raise ValueError(
            "config is not class-batchable (inverse_engine='chol' batched "
            "triangular solves are not vmap-bit-stable); use sequential fits"
        )
    dtype = config.jax_dtype()
    Xs = [np.asarray(X) for X in Xs]
    if len(Xs) == 0:
        return []
    if len(Xs) == 1:
        # XLA folds size-1 batch dims into different fusions than k >= 2
        # (observed: the scalar reductions change bits at k=1 only), so a
        # lone class rides with a discarded copy of itself — results are
        # then independent of batch composition for every k.
        return fit_classes(
            [Xs[0], Xs[0]], config, mesh=mesh, data_axes=data_axes, m_cap=m_cap
        )[:1]
    k = len(Xs)
    n = Xs[0].shape[1]
    if any(X.ndim != 2 or X.shape[1] != n for X in Xs):
        raise ValueError("all classes must be (m_c, n) with one shared n")
    ms = [X.shape[0] for X in Xs]

    group = next(_GROUP_IDS)
    batch = {
        "group": group,
        "size": k,
        "m_cap": 0,  # filled once the shared row bucket is known
        "recompiles": 0,
        "regrowths": 0,
        "degree_times": [],
        "gram_tiles": 0,
        "m": int(sum(ms)),
        "n": n,
    }
    scope = FitScope(batch, backend="class_batch")
    with scope:
        with obs.span("fit/prepare"):
            # per-class Pearson ordering (each class permutes its own features)
            perms: List[Optional[np.ndarray]] = []
            Xp: List[np.ndarray] = []
            for X in Xs:
                perm = None
                if config.ordering in ("pearson", "reverse_pearson"):
                    perm = pearson_order(X, reverse=(config.ordering == "reverse_pearson"))
                    X = X[:, perm]
                perms.append(perm)
                Xp.append(X)

            shards = 1
            if mesh is not None:
                from . import distributed as distributed_mod

                shards = distributed_mod.num_data_shards(mesh, data_axes)
            mc = m_cap if m_cap is not None else pow2_bucket(max(ms))
            mc = _round_up(max(mc, max(ms)), shards)
            batch["m_cap"] = int(mc)

            # stacked rows + per-class row masks (mask IS the constant column, so
            # padded rows are zero in every column of A)
            np_dt = _np_dtype(config.dtype)
            Xstack = np.zeros((k, mc, n), np_dt)
            mask = np.zeros((k, mc), np_dt)
            for c, X in enumerate(Xp):
                Xstack[c, : ms[c]] = X
                mask[c, : ms[c]] = 1.0
            Lcap = pow2_bucket(config.cap_terms)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from . import distributed as distributed_mod

                # host -> row shards directly: no device ever holds all rows
                bspec = NamedSharding(mesh, distributed_mod.class_data_spec(data_axes))
                rep = NamedSharding(mesh, P())
                Xd = jax.device_put(Xstack, bspec)
                mask_d = jax.device_put(mask, NamedSharding(mesh, P(*bspec.spec[:2])))
            else:
                bspec = rep = None
                Xd = jnp.asarray(Xstack)
                mask_d = jnp.asarray(mask)
            A, state = _init_batch_arrays(mask_d, Lcap, config.ihb_factors())
            if mesh is not None:
                A = jax.device_put(A, bspec)
                state = jax.device_put(state, rep)

            books = [terms_mod.TermBook(n=n) for _ in range(k)]
            generators: List[List[Generator]] = [[] for _ in range(k)]
            ells = [1] * k
            active = [True] * k

            m_total = jnp.asarray([float(m) for m in ms], dtype)

            per_class = [init_fit_stats(ms[c], n) for c in range(k)]

        d = 0
        while any(active):
            d += 1
            if d > config.max_degree:
                for c in range(k):
                    if active[c]:
                        per_class[c]["termination"] = f"max_degree={config.max_degree}"
                break
            with obs.span("fit/border"):
                borders: List[List] = []
                for c in range(k):
                    b = books[c].border(d) if active[c] else []
                    if active[c] and not b:
                        active[c] = False
                        per_class[c]["termination"] = "empty_border"
                    borders.append(b)
                if not any(active):
                    break
                Ks = [len(b) for b in borders]
                for c in range(k):
                    if borders[c]:
                        per_class[c]["border_sizes"].append(Ks[c])
                        per_class[c]["degrees"].append(d)

                # shared capacity: regrow when the largest class overflows
                while max(ells[c] + Ks[c] for c in range(k)) > Lcap:
                    A = jnp.pad(A, ((0, 0), (0, 0), (0, Lcap)))  # keeps A's sharding
                    Lcap *= 2
                    scope.regrowth(Lcap)
                    state = ihb_mod.grow_state(state, Lcap)
                    if mesh is not None:
                        A = jax.device_put(A, bspec)
                        state = jax.device_put(state, rep)
                Kcap = max(config.cap_border, pow2_bucket(max(Ks)))
                parents = np.zeros((k, Kcap), np.int32)
                vars_ = np.zeros((k, Kcap), np.int32)
                valid = np.zeros((k, Kcap), bool)  # done classes: all-False -> no-op
                for c in range(k):
                    if borders[c]:
                        parents[c], vars_[c], valid[c] = border_index_arrays(
                            books[c], borders[c], Kcap
                        )

                # the Gram kernel's output tiles at this degree's capacities
                tiles = kernel_ops.gram_tiles(Lcap, Kcap, n)
                batch["gram_tiles"] += tiles
                obs.registry().counter("gram/tiles").inc(tiles)

                ells_d = jnp.asarray(ells, jnp.int32)
                parents_d = jnp.asarray(parents)
                vars_d = jnp.asarray(vars_)
                valid_d = jnp.asarray(valid)

            with scope.degree(d, K=int(max(Ks)), k=k):
                entry = _batched_entry(config, mesh, data_axes)
                scope.note_signature(entry.seen, (k, mc, n, Lcap, Kcap, str(dtype)))
                A, st = entry.fn(
                    A, Xd, state, ells_d, parents_d, vars_d, valid_d, m_total
                )
                # one host sync per degree
                accepted, mses, coeffs, iters = jax.device_get(
                    (st.accepted, st.mses, st.coeffs, st.iters)
                )
                state = st.ihb

            with obs.span("fit/collect"):
                for c in range(k):
                    if not borders[c]:
                        continue
                    per_class[c]["solver_iters"].append(int(iters[c, : Ks[c]].sum()))
                    ells[c] = collect_degree(
                        books[c], borders[c], accepted[c], mses[c], coeffs[c],
                        generators[c],
                    )

        models: List[OAVIModel] = []
        for c in range(k):
            stats = per_class[c]
            # shared per-batch quantities: one compile/regrowth schedule and one
            # wall clock serve all k classes (aggregate once per group)
            stats["recompiles"] = batch["recompiles"]
            stats["regrowths"] = batch["regrowths"]
            stats["degree_times"] = list(batch["degree_times"])
            stats["gram_tiles"] = batch["gram_tiles"]
            stats["class_batch"] = {
                "group": batch["group"],
                "size": k,
                "index": c,
                "m_cap": batch["m_cap"],
                "recompiles": batch["recompiles"],
                "regrowths": batch["regrowths"],
            }
            scope.finalize(books[c], generators[c], Lcap, config, stats=stats)
            models.append(
                OAVIModel(
                    n=n,
                    psi=config.psi,
                    book=books[c],
                    generators=generators[c],
                    feature_perm=perms[c],
                    stats=stats,
                    dtype=config.dtype,
                )
            )
    return models


def class_buckets(sizes: Sequence[int]) -> Dict[int, List[int]]:
    """Group class indices into shared row buckets (greedy, largest first):
    every class with ``m >= cap/2`` joins the bucket ``cap =
    pow2_bucket(largest remaining m)``, so per-class row padding stays <= 2x.
    With lognormal-skewed class sizes this keeps a giant class from
    inflating every small class's padded rows."""
    order = sorted(range(len(sizes)), key=lambda i: -sizes[i])
    buckets: Dict[int, List[int]] = {}
    i = 0
    while i < len(order):
        cap = pow2_bucket(sizes[order[i]])
        group = [j for j in order[i:] if 2 * sizes[j] >= cap]
        buckets[cap] = sorted(group)
        i += len(group)
    return buckets


def plan_class_groups(
    sizes: Sequence[int], pad_limit: float = 2.0
) -> List[tuple]:
    """Plan the shared row buckets of a multi-class fit as ``[(m_cap,
    class_indices), ...]`` — :func:`class_buckets` plus two refinements that
    trade padded rows for fewer dispatch groups:

    1. **Cross-bucket merging** (largest cap first): a smaller bucket folds
       into the preceding larger one while the merged group's total padded
       rows stay within ``pad_limit`` of its real rows, so near-boundary
       buckets don't each pay their own compile/dispatch pipeline.
    2. **No stragglers**: any group left with a single class is folded —
       unconditionally — into whichever surviving group grows its padded-row
       bill the least.  A size-1 "batch" would otherwise fall back to a
       sequential fit (a cold compile for exactly one class); eating some
       padding on an already-warm bucket is strictly cheaper.

    The resulting per-class padding is reported by the API layer in
    ``stats["class_batch_padding"]``.
    """
    if len(sizes) == 0:
        return []
    buckets = class_buckets(sizes)
    groups = [
        [cap, list(idxs)] for cap, idxs in sorted(buckets.items(), reverse=True)
    ]
    merged = [groups[0]]
    for cap, idxs in groups[1:]:
        host = merged[-1]
        count = len(host[1]) + len(idxs)
        real = sum(sizes[i] for i in host[1]) + sum(sizes[i] for i in idxs)
        if host[0] * count <= pad_limit * real:
            host[1] = sorted(host[1] + idxs)
        else:
            merged.append([cap, list(idxs)])
    while len(merged) > 1:
        singles = [g for g in merged if len(g[1]) == 1]
        if not singles:
            break
        g = singles[0]
        merged.remove(g)
        s = sizes[g[1][0]]

        def extra(h):
            new_cap = max(h[0], pow2_bucket(s))
            return new_cap * (len(h[1]) + 1) - h[0] * len(h[1])

        host = min(merged, key=extra)
        host[0] = max(host[0], pow2_bucket(s))
        host[1] = sorted(host[1] + g[1])
    return [(int(cap), idxs) for cap, idxs in merged]
