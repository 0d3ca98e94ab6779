"""Out-of-core OAVI: fit over data that never fully resides on device.

The paper's central scaling observation is that every degree-step decision of
OAVI reduces to ``O(|O| * |border|)`` Gram sufficient statistics — the
``(m, Lcap)`` evaluation matrix A only ever enters through ``A^T B`` and
``B^T B``.  The in-memory fit still materializes A (capping ``m`` at device
memory); this driver does not:

* **Per-degree A rematerialization** — a column of A is exactly the
  evaluation of an O term, so for each fixed-size row chunk of X the A-block
  is rebuilt from scratch with the degree-wavefront term evaluator
  (:func:`repro.core.oavi.apply_wavefronts`, bit-identical to the
  incrementally-built A: both multiply parent column by variable column in
  the same association order).
* **Streaming Gram accumulation** — each chunk's Gram blocks fold into
  running ``(Lcap, Kcap)`` / ``(Kcap, Kcap)`` fp32 accumulators through
  :func:`repro.kernels.ops.gram_accumulate`, whose ``GRAM_BLOCK``-row
  sequential reduction makes the accumulated statistics *bit-identical* to
  the in-memory degree step's single call — for any chunk size that is a
  multiple of ``GRAM_BLOCK`` — so the streamed fit reproduces the in-memory
  fit exactly at matched capacity.
* **Statistics-only degree step** — the acceptance loop runs on the
  accumulated statistics alone (:func:`repro.core.oavi._make_stats_degree_step`,
  hoisted out of the in-memory step), covering both the closed-form ``fast``
  engine and the convex-oracle configs (their IHB/AtA state is Gram-only).
* **Sharding** — with a ``mesh``, each data shard streams the chunks of its
  contiguous row span (the same row partition as
  :func:`repro.core.distributed.fit`) into per-shard accumulators held
  device-side under ``shard_map``; ONE psum of the accumulated statistics
  per degree — the same collective count as the in-memory sharded fit, and
  bit-identical to it at matched capacity.

Peak device memory is O(chunk_rows * Lcap) + O(Lcap^2) regardless of ``m``:
the half of the paper's "linear in m" claim that device memory previously
denied us.
"""

from __future__ import annotations

from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..kernels import ops as kernel_ops
from ..core import ihb as ihb_mod
from ..core import terms as terms_mod
from ..core.distributed import (
    data_spec,
    num_data_shards,
    shard_probe,
)
from ..core.oavi import (
    FitScope,
    Generator,
    OAVIConfig,
    OAVIModel,
    _kernel_kwargs,
    _make_stats_degree_step,
    _np_dtype,
    apply_wavefronts,
    border_index_arrays,
    class_batchable,
    collect_degree,
    degree_step_entry,
    init_fit_stats,
    pow2_bucket,
    wavefront_schedule,
)
from ..core.ordering import pearson_order_from_moments
from .source import DataSource, as_source, iter_chunks

DEFAULT_CHUNK_ROWS = 4096


def _check_chunk_rows(chunk_rows: int) -> int:
    chunk_rows = int(chunk_rows)
    if chunk_rows < kernel_ops.GRAM_BLOCK or chunk_rows & (chunk_rows - 1):
        raise ValueError(
            f"chunk_rows must be a power of two >= {kernel_ops.GRAM_BLOCK} "
            f"(the canonical Gram block), got {chunk_rows}"
        )
    return chunk_rows


def pearson_moments(
    source: DataSource,
    chunk_rows: int,
    start: int = 0,
    stop: Optional[int] = None,
    s1: Optional[np.ndarray] = None,
    s2: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fold rows ``[start, stop)`` of ``source`` into the float64 Pearson
    sufficient statistics ``(s1, s2) = (sum x, sum x x^T)`` — the one-pass
    moment state behind :func:`streaming_pearson_order`, exposed so an
    online fit can persist it and fold only *new* rows on update."""
    n = source.num_features
    s1 = np.zeros((n,), np.float64) if s1 is None else np.array(s1, np.float64)
    s2 = np.zeros((n, n), np.float64) if s2 is None else np.array(s2, np.float64)
    for chunk, valid in iter_chunks(source, chunk_rows, start=start, stop=stop):
        rows = np.asarray(chunk[:valid], np.float64)
        s1 += rows.sum(axis=0)
        s2 += rows.T @ rows
    return s1, s2


def streaming_pearson_order(
    source: DataSource, chunk_rows: int, reverse: bool = False
) -> np.ndarray:
    """One streaming pass of float64 sufficient statistics -> Pearson feature
    order (Algorithm 5).  See :func:`pearson_scores_from_moments` for the
    (ulp-level, tie-only) caveat vs the in-memory two-pass formula."""
    s1, s2 = pearson_moments(source, chunk_rows)
    return pearson_order_from_moments(s1, s2, source.num_rows, reverse=reverse)


def prefetch_map(stage, items: Iterable, enabled: bool = True):
    """Yield ``stage(item)`` for each item, keeping ONE staged result in
    flight ahead of the consumer (host->device double buffering).

    While the consumer runs the jitted accumulator on chunk ``i``, a single
    worker thread assembles and device-puts chunk ``i+1`` — the host-side
    read/pad/transfer work overlaps the device work instead of serializing
    with it.  Order is preserved and every item is staged exactly once, so
    the values the consumer folds are identical with prefetching on or off
    (bit-identity is a pure function of the fold order, which this never
    changes)."""
    if not enabled:
        for item in items:
            yield stage(item)
        return
    with ThreadPoolExecutor(max_workers=1) as pool:
        pending = None
        for item in items:
            nxt = pool.submit(stage, item)
            if pending is not None:
                yield pending.result()
            pending = nxt
        if pending is not None:
            yield pending.result()


# ---------------------------------------------------------------------------
# Chunk accumulator: jitted (rematerialize A-block, fold Gram blocks) per book
# ---------------------------------------------------------------------------

# LRU-bounded like the wavefront cache: one entry per (book, config, shapes);
# a warm refit of the same data replays the same book sequence and compiles
# nothing.
_ACC_CACHE: "OrderedDict[Tuple, Tuple]" = OrderedDict()
_ACC_CACHE_SIZE = 64


def _chunk_accumulator(
    book: terms_mod.TermBook,
    cfg: OAVIConfig,
    Lcap: int,
    chunk_rows: int,
    mesh: Optional[Mesh],
    data_axes: Tuple[str, ...],
):
    """Jitted ``(accQL, accC, Xc, mask, parents, vars_) -> (accQL, accC)``
    for one term book: rematerialize the chunk's A-block with the wavefront
    evaluator, fold its Gram blocks into the running accumulators (donated,
    so the buffers are reused in place).  Returns ``(fn, seen, is_new)``;
    ``seen`` mirrors the jit trace cache for recompile accounting."""
    parents_np = np.asarray(book.parents, np.int32)
    vars_np = np.asarray(book.vars, np.int32)
    key = (
        parents_np.tobytes(),
        vars_np.tobytes(),
        cfg,
        Lcap,
        chunk_rows,
        mesh,
        data_axes,
    )
    cached = _ACC_CACHE.get(key)
    if cached is not None:
        _ACC_CACHE.move_to_end(key)
        return cached[0], cached[1], False

    waves, wperm = wavefront_schedule(parents_np, vars_np)
    ell_book = len(book)
    gram_kw = _kernel_kwargs(cfg)

    def body(accQL, accC, Xc, mask, parents, vars_):
        # A-block = O-term evaluations of this chunk: bit-identical to the
        # incrementally built A (same parent-times-variable association).
        cols = apply_wavefronts(Xc, waves, wperm)
        # padded chunk rows must be zero in EVERY column (the constant column
        # doubles as the row mask, like the sharded path); real rows multiply
        # by exactly 1.0
        cols = cols * mask[:, None]
        A = jnp.pad(cols, ((0, 0), (0, Lcap - ell_book)))
        return kernel_ops.gram_accumulate(
            A, Xc, parents, vars_, acc=(accQL, accC), **gram_kw
        )

    if mesh is None:
        fn = jax.jit(body, donate_argnums=(0, 1))
    else:
        dspec2 = data_spec(data_axes)
        dspec1 = P(data_axes if len(data_axes) > 1 else data_axes[0])
        aspec = P(data_axes if len(data_axes) > 1 else data_axes[0], None, None)
        rep = P()

        def per_shard(accQL, accC, Xc, mask, parents, vars_):
            ql, c = body(accQL[0], accC[0], Xc, mask, parents, vars_)
            return ql[None], c[None]

        fn = jax.jit(
            jax.shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(aspec, aspec, dspec2, dspec1, rep, rep),
                out_specs=(aspec, aspec),
                check_vma=False,
            ),
            donate_argnums=(0, 1),
        )
    entry = (fn, set())
    _ACC_CACHE[key] = entry
    if len(_ACC_CACHE) > _ACC_CACHE_SIZE:
        _ACC_CACHE.popitem(last=False)
    return fn, entry[1], True


def accumulate_source_range(
    acc_fn,
    source: DataSource,
    start: int,
    stop: int,
    chunk_rows: int,
    acc: Tuple[jax.Array, jax.Array],
    parents_d: jax.Array,
    vars_d: jax.Array,
    perm: Optional[np.ndarray] = None,
    np_dtype=np.float32,
    prefetch: bool = True,
) -> Tuple[jax.Array, jax.Array, int]:
    """Fold rows ``[start, stop)`` of ``source`` into the Gram accumulators
    through one jitted chunk accumulator (local path).

    ``start`` must sit on a :data:`~repro.kernels.ops.GRAM_BLOCK` boundary of
    the *global* row index: every chunk then covers whole GRAM_BLOCK blocks
    (trailing zero-padding is a bitwise no-op), so the block partition — and
    therefore every fp32 partial — is identical to a single pass over
    ``[0, stop)`` no matter where the range is split.  This is what lets an
    online update resume accumulation exactly where a previous fit's
    statistics end (:mod:`repro.online`).  Returns
    ``(accQL, accC, num_chunks)``."""
    if start % kernel_ops.GRAM_BLOCK:
        raise ValueError(
            f"range start {start} is not a multiple of the Gram block "
            f"({kernel_ops.GRAM_BLOCK}); the blocked fp32 reduction would "
            "not match a one-shot pass bit for bit"
        )
    n = source.num_features

    def stage(lo: int):
        hi = min(lo + chunk_rows, stop)
        rows = np.zeros((chunk_rows, n), np_dtype)
        mask = np.zeros((chunk_rows,), np_dtype)
        block = np.asarray(source.read(lo, hi))
        if perm is not None:
            block = block[:, perm]
        rows[: hi - lo] = block
        mask[: hi - lo] = 1.0
        return jnp.asarray(rows), jnp.asarray(mask)

    accQL, accC = acc
    num_chunks = 0
    steps = range(start, stop, chunk_rows)
    with obs.span("streaming/accumulate", start=start, stop=stop,
                  chunk_rows=chunk_rows):
        for rows_d, mask_d in prefetch_map(stage, steps, enabled=prefetch):
            accQL, accC = acc_fn(accQL, accC, rows_d, mask_d, parents_d, vars_d)
            num_chunks += 1
    return accQL, accC, num_chunks


def _streaming_stats_entry(
    config: OAVIConfig, mesh: Optional[Mesh], data_axes: Tuple[str, ...]
):
    """Cached jitted statistics-only degree step — replicated stats loop
    locally; under ``shard_map`` with ONE psum of the accumulators per degree
    when sharded."""
    if mesh is None:
        return degree_step_entry(
            config,
            backend_key="streaming",
            jitted_builder=lambda: jax.jit(_make_stats_degree_step(config)),
        )

    def build():
        axes = tuple(data_axes)
        reduce_fn = lambda x: jax.lax.psum(x, axes)  # noqa: E731
        stats_step = _make_stats_degree_step(config, reduce_fn=reduce_fn)
        aspec = P(axes if len(axes) > 1 else axes[0], None, None)
        rep = P()

        def per_shard(accQL, accC, state, ell0, valid, m_total):
            return stats_step(accQL[0], accC[0], state, ell0, valid, m_total)

        # per-shard instant marker, once per degree (NOT on the per-chunk
        # accumulator hot path) — the sharded streaming half of the PR 8
        # span-coverage remainder
        per_shard = shard_probe(per_shard, mesh, axes, "fit/shard_step")

        return jax.jit(
            jax.shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(aspec, aspec, rep, rep, rep, rep),
                out_specs=rep,
                check_vma=False,
            )
        )

    return degree_step_entry(
        config, backend_key=("streaming", mesh, tuple(data_axes)), jitted_builder=build
    )


# ---------------------------------------------------------------------------
# The streaming fit driver
# ---------------------------------------------------------------------------


def fit(
    source,
    config: OAVIConfig = OAVIConfig(),
    *,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
    prefetch: bool = True,
) -> OAVIModel:
    """Run OAVI over a chunked :class:`~repro.streaming.source.DataSource`
    (or array-like) without ever materializing the evaluation matrix.

    Same semantics as :func:`repro.core.oavi.fit` — bit-exact against it at
    matched capacity for any power-of-two ``chunk_rows`` that is a multiple
    of :data:`repro.kernels.ops.GRAM_BLOCK` (and against
    :func:`repro.core.distributed.fit` on the same ``mesh`` when sharded).
    ``source`` must yield data in ``[0, 1]^n`` (compose with
    :class:`~repro.streaming.source.ScaledSource`).

    ``prefetch`` double-buffers the host->device pipeline: chunk ``i+1`` is
    read, permuted, padded and transferred by a worker thread while chunk
    ``i``'s jitted accumulator runs (:func:`prefetch_map`).  The fold order
    is unchanged, so the result is bit-identical with it on or off.
    """
    source = as_source(source)
    chunk_rows = _check_chunk_rows(chunk_rows)
    dtype = config.jax_dtype()
    np_dtype = _np_dtype(config.dtype)
    m, n = source.num_rows, source.num_features
    axes = tuple(data_axes)
    stats = init_fit_stats(
        m, n, streaming={"chunk_rows": chunk_rows, "num_chunks": 0, "passes": 0}
    )
    if mesh is not None:
        stats["mesh"] = {a: int(mesh.shape[a]) for a in mesh.axis_names}
        stats["data_axes"] = list(axes)
    backend = "streaming" if mesh is None else "streaming_sharded"

    with FitScope(stats, backend=backend) as scope:
        perm = None
        if config.ordering in ("pearson", "reverse_pearson"):
            perm = streaming_pearson_order(
                source, chunk_rows, reverse=(config.ordering == "reverse_pearson")
            )

        book = terms_mod.TermBook(n=n)
        generators: List[Generator] = []

        Lcap = pow2_bucket(config.cap_terms)
        state = ihb_mod.init_state(
            Lcap, jnp.asarray(1.0, dtype), dtype, factors=config.ihb_factors()
        )
        ell = 1

        # sharded layout: the SAME contiguous per-shard row spans as the
        # in-memory distributed fit, so per-shard partials (and their psum)
        # are bit-identical to it
        if mesh is not None:
            shards = num_data_shards(mesh, axes)
            m_pad = ((m + shards - 1) // shards) * shards
            span = m_pad // shards
            dspec = data_spec(axes)
            chunk_sharding = NamedSharding(mesh, dspec)
            mask_sharding = NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))
            acc_sharding = NamedSharding(
                mesh, P(axes if len(axes) > 1 else axes[0], None, None)
            )
            rep_sharding = NamedSharding(mesh, P())
            state = jax.device_put(state, rep_sharding)
            stats["m_padded"] = m_pad
        else:
            shards = 1
            span = m

        entry = _streaming_stats_entry(config, mesh, axes)
        m_total = jnp.asarray(float(m), dtype)
        steps_per_pass = max((span + chunk_rows - 1) // chunk_rows, 1)

        def load_step(i: int) -> Tuple[np.ndarray, np.ndarray]:
            """Host-side chunk assembly for global step ``i``: each shard's
            rows ``[s*span + i*c, ...)`` of its span, zero-padded, plus the
            row mask."""
            c = chunk_rows
            rows = np.zeros((shards * c, n), np_dtype)
            mask = np.zeros((shards * c,), np_dtype)
            for s in range(shards):
                lo = s * span + i * c
                hi = min(lo + c, (s + 1) * span, m)
                if lo >= hi:
                    continue
                block = np.asarray(source.read(lo, hi))
                if perm is not None:
                    block = block[:, perm]
                rows[s * c : s * c + hi - lo] = block
                mask[s * c : s * c + hi - lo] = 1.0
            return rows, mask

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

            # capacity management: only the O(Lcap^2) state grows — there is
            # no (m, Lcap) buffer to regrow, which is the whole point
            while ell + K > Lcap:
                Lcap *= 2
                scope.regrowth(Lcap)
                state = ihb_mod.grow_state(state, Lcap)
                if mesh is not None:
                    state = jax.device_put(state, rep_sharding)

            Kcap = max(config.cap_border, pow2_bucket(K))
            parents, vars_, valid = border_index_arrays(book, border, Kcap)

            acc_fn, acc_seen, acc_new = _chunk_accumulator(
                book, config, Lcap, chunk_rows, mesh, axes
            )
            # a fresh accumulator fn (acc_new) starts with an empty ``seen``,
            # so its first signature always counts — same rule as before
            acc_sig = (Kcap, chunk_rows, n, str(dtype))
            scope.note_signature(acc_seen, acc_sig, kind="fit/compile_accumulator")
            sig = (Lcap, Kcap, str(dtype))
            scope.note_signature(entry.seen, sig)

            sample_chunks = obs.device.device_enabled()

            with scope.degree(d, K=K):
                parents_d = jnp.asarray(parents)
                vars_d = jnp.asarray(vars_)
                if mesh is None:
                    accQL = jnp.zeros((Lcap, Kcap), jnp.float32)
                    accC = jnp.zeros((Kcap, Kcap), jnp.float32)
                else:
                    accQL = jax.device_put(
                        jnp.zeros((shards, Lcap, Kcap), jnp.float32), acc_sharding
                    )
                    accC = jax.device_put(
                        jnp.zeros((shards, Kcap, Kcap), jnp.float32), acc_sharding
                    )

                def stage(i: int):
                    rows, mask = load_step(i)
                    if mesh is None:
                        return jnp.asarray(rows), jnp.asarray(mask)
                    return (
                        jax.device_put(rows, chunk_sharding),
                        jax.device_put(mask, mask_sharding),
                    )

                with obs.span("streaming/accumulate", d=d, chunks=steps_per_pass):
                    for rows_d, mask_d in prefetch_map(
                        stage, range(steps_per_pass), enabled=prefetch
                    ):
                        accQL, accC = acc_fn(
                            accQL, accC, rows_d, mask_d, parents_d, vars_d
                        )
                        if sample_chunks:
                            # chunk-boundary memory timeline (gauges + trace
                            # counter); intra-degree peaks are invisible to
                            # the per-degree sample alone
                            obs.device.sample_memory(stats)
                stats["streaming"]["num_chunks"] += steps_per_pass
                stats["streaming"]["passes"] += 1

                st = entry.fn(
                    accQL,
                    accC,
                    state,
                    jnp.asarray(ell, jnp.int32),
                    jnp.asarray(valid),
                    m_total,
                )
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


# ---------------------------------------------------------------------------
# Class-batched streaming fit: k out-of-core fits, ONE vmapped stats step
# ---------------------------------------------------------------------------


def _streaming_class_entry(config: OAVIConfig, schedule):
    """Cached jitted ``vmap`` of the statistics-only degree step over a class
    axis; ``schedule`` (oracle/WIHB configs) is part of the cache key so each
    escalation level is its own compiled step."""
    return degree_step_entry(
        config,
        backend_key=("streaming_class_batch", schedule),
        jitted_builder=lambda: jax.jit(
            jax.vmap(_make_stats_degree_step(config, schedule=schedule))
        ),
    )


def fit_classes(
    sources: Sequence,
    config: OAVIConfig = OAVIConfig(),
    *,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    prefetch: bool = True,
) -> List[OAVIModel]:
    """Fit one OAVI model per class out-of-core, with every class's
    accept/reject decisions batched through ONE vmapped statistics-only
    degree step per degree.

    Unlike the in-memory class batch (:mod:`repro.core.class_batch`) there is
    no shared row bucket and no row padding at all: each class streams its
    own rows through its own chunk accumulator (the per-degree O(m_c) work is
    inherently per-class), and only the m-independent acceptance loops — the
    dispatch-bound part of a streaming fit — are stacked into ``(k, Lcap,
    Kcap)`` statistics and decided in one dispatch.  Finished classes ride
    along with all-``False`` validity masks (their zeroed accumulators make
    the slice a bitwise no-op); oracle/WIHB configs run the fixed-schedule
    solvers with the same budget-escalation protocol as the in-memory batch
    (the stats step donates nothing, so re-dispatch is safe).

    Bit-exact against per-class :func:`fit` calls at matched capacity (the
    shared ``Lcap`` growth schedule — the accumulated statistics themselves
    are per-class and identical by construction).  Local backend only; the
    sharded streaming path stays per-class.
    """
    from ..core import class_batch as class_batch_mod
    from ..core import oracles as oracles_mod

    sources = [as_source(s) for s in sources]
    chunk_rows = _check_chunk_rows(chunk_rows)
    if not class_batchable(config):
        raise ValueError(
            "config is not class-batchable (inverse_engine='chol' batched "
            "triangular solves are not vmap-bit-stable); use sequential fits"
        )
    if len(sources) == 0:
        return []
    if len(sources) == 1:
        # mirror class_batch.fit_classes: a lone class rides with a discarded
        # duplicate so results are independent of batch composition at k=1
        return fit_classes(
            [sources[0], sources[0]], config,
            chunk_rows=chunk_rows, prefetch=prefetch,
        )[:1]
    k = len(sources)
    n = sources[0].num_features
    if any(s.num_features != n for s in sources):
        raise ValueError("all classes must share one feature count n")
    ms = [s.num_rows for s in sources]
    dtype = config.jax_dtype()
    np_dtype = _np_dtype(config.dtype)

    group = next(class_batch_mod._GROUP_IDS)
    batch = {
        "group": group,
        "size": k,
        "recompiles": 0,
        "regrowths": 0,
        "degree_times": [],
        "m": int(sum(ms)),
        "n": n,
    }
    scope = FitScope(batch, backend="streaming_class_batch")
    with scope:
        perms: List[Optional[np.ndarray]] = []
        for s in sources:
            perm = None
            if config.ordering in ("pearson", "reverse_pearson"):
                perm = streaming_pearson_order(
                    s, chunk_rows, reverse=(config.ordering == "reverse_pearson")
                )
            perms.append(perm)

        books = [terms_mod.TermBook(n=n) for _ in range(k)]
        generators: List[List[Generator]] = [[] for _ in range(k)]
        ells = [1] * k
        active = [True] * k

        Lcap = pow2_bucket(config.cap_terms)
        state = ihb_mod.batch_state(
            ihb_mod.init_state(
                Lcap, jnp.asarray(1.0, dtype), dtype, factors=config.ihb_factors()
            ),
            k,
        )
        schedule = (
            oracles_mod.schedule_budget(config.solver)
            if class_batch_mod.needs_solver_schedule(config)
            else None
        )
        batch["solver_escalations"] = 0

        m_total = jnp.asarray([float(m) for m in ms], dtype)
        per_class = [
            init_fit_stats(
                ms[c], n,
                streaming={"chunk_rows": chunk_rows, "num_chunks": 0, "passes": 0},
            )
            for c in range(k)
        ]

        d = 0
        while any(active):
            d += 1
            if d > config.max_degree:
                for c in range(k):
                    if active[c]:
                        per_class[c]["termination"] = f"max_degree={config.max_degree}"
                break
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

            while max(ells[c] + Ks[c] for c in range(k)) > Lcap:
                Lcap *= 2
                scope.regrowth(Lcap)
                state = ihb_mod.grow_state(state, Lcap)
            Kcap = max(config.cap_border, pow2_bucket(max(Ks)))
            valid = np.zeros((k, Kcap), bool)

            with scope.degree(d, K=int(max(Ks)), k=k):
                # per-class accumulation: each class streams its own rows
                # through its own (book-keyed) chunk accumulator — identical
                # statistics to its single-class streaming fit
                accQLs = []
                accCs = []
                for c in range(k):
                    if not borders[c]:
                        accQLs.append(jnp.zeros((Lcap, Kcap), jnp.float32))
                        accCs.append(jnp.zeros((Kcap, Kcap), jnp.float32))
                        continue
                    parents_c, vars_c, valid[c] = border_index_arrays(
                        books[c], borders[c], Kcap
                    )
                    acc_fn, acc_seen, _ = _chunk_accumulator(
                        books[c], config, Lcap, chunk_rows, None, ()
                    )
                    scope.note_signature(
                        acc_seen, (Kcap, chunk_rows, n, str(dtype)),
                        kind="fit/compile_accumulator",
                    )
                    accQL, accC, nchunks = accumulate_source_range(
                        acc_fn,
                        sources[c],
                        0,
                        ms[c],
                        chunk_rows,
                        (
                            jnp.zeros((Lcap, Kcap), jnp.float32),
                            jnp.zeros((Kcap, Kcap), jnp.float32),
                        ),
                        jnp.asarray(parents_c),
                        jnp.asarray(vars_c),
                        perm=perms[c],
                        np_dtype=np_dtype,
                        prefetch=prefetch,
                    )
                    per_class[c]["streaming"]["num_chunks"] += nchunks
                    per_class[c]["streaming"]["passes"] += 1
                    accQLs.append(accQL)
                    accCs.append(accC)

                accQL_b = jnp.stack(accQLs)
                accC_b = jnp.stack(accCs)
                ells_d = jnp.asarray(ells, jnp.int32)
                valid_d = jnp.asarray(valid)

                # ONE vmapped stats step for all classes; escalate the solver
                # schedule while any valid lane's budget was cut short
                while True:
                    entry = _streaming_class_entry(config, schedule)
                    csig = (k, Lcap, Kcap, str(dtype), schedule)
                    cargs = (accQL_b, accC_b, state, ells_d, valid_d, m_total)
                    scope.note_signature(entry.seen, csig)
                    st = entry.fn(*cargs)
                    if schedule is None or not bool(
                        np.any(jax.device_get(st.unconverged))
                    ):
                        break
                    if schedule >= oracles_mod.max_schedule(config.solver):
                        break
                    schedule = oracles_mod.escalate_schedule(config.solver, schedule)
                    batch["solver_escalations"] += 1
                state = st.ihb
                accepted, mses, coeffs, iters = jax.device_get(
                    (st.accepted, st.mses, st.coeffs, st.iters)
                )

            for c in range(k):
                if not borders[c]:
                    continue
                per_class[c]["solver_iters"].append(int(iters[c, : Ks[c]].sum()))
                ells[c] = collect_degree(
                    books[c], borders[c], accepted[c], mses[c], coeffs[c],
                    generators[c],
                )

        batch["solver_schedule_len"] = schedule
        if schedule is not None:
            obs.registry().gauge(
                "fit.solver_schedule_len", backend="streaming_class_batch"
            ).set(float(schedule))
        if batch["solver_escalations"]:
            obs.registry().counter(
                "fit.solver_escalations", backend="streaming_class_batch"
            ).inc(batch["solver_escalations"])
        models: List[OAVIModel] = []
        for c in range(k):
            stats = per_class[c]
            stats["recompiles"] = batch["recompiles"]
            stats["regrowths"] = batch["regrowths"]
            stats["degree_times"] = list(batch["degree_times"])
            stats["solver_schedule_len"] = schedule
            stats["solver_escalations"] = batch["solver_escalations"]
            stats["class_batch"] = {
                "group": batch["group"],
                "size": k,
                "index": c,
                "m_cap": None,  # streaming: no shared row bucket, no row padding
                "streaming": True,
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
