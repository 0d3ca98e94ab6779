"""OAVI — the Oracle Approximate Vanishing Ideal algorithm (Algorithm 1).

Structure
---------
Host-side Python owns the *combinatorics* (term book, DegLex borders — a few
hundred items, Theorem 4.3), jitted JAX owns the *linear algebra*.  Per degree
``d`` the whole border is processed by one jitted ``_degree_step``:

1.  Candidate columns ``B = A[:, parents] * X[:, vars]``  (gather + product)
2.  Gram blocks   ``QL = A^T B`` (L x K) and ``C = B^T B`` (K x K)
    — these two matmuls are the *only* O(m) work in the whole degree.  They
    are computed by :func:`repro.kernels.ops.gram_accumulate`: the fused
    Pallas kernel on TPU (border evaluation + both Grams in one VMEM-resident
    sweep), the bit-identical blocked reference elsewhere.  The reduction
    order is *canonical* (sequential fp32 accumulation over ``GRAM_BLOCK``
    row blocks), which is what lets the out-of-core fit
    (:mod:`repro.streaming.fit`) stream row chunks through the same op and
    land on identical bits.
3.  A small ``fori_loop`` over the K candidates replays the exact sequential
    semantics of Algorithm 1 (a term appended to O changes A for all later
    candidates of the same degree) using only the precomputed Gram blocks:
    the ``A^T b`` vector of candidate ``a`` is ``QL[:, a]`` plus ``C[j, a]``
    scattered into the slots of the candidates ``j < a`` appended this degree.

This "degree-batched Gram" formulation is bit-exact w.r.t. the sequential
algorithm yet makes OAVI matmul-bound (MXU-friendly) — the per-candidate work
inside the loop is O(l^2), independent of m.  It is also the unit of
distribution: with X sharded over samples, step (1)+(2) are local matmuls
followed by a psum of (L x K) + (K x K) buffers (see
:mod:`repro.core.distributed`).

Capacities and recompiles
-------------------------
``|O|`` capacity (``Lcap``) and border capacity (``Kcap``) are power-of-two
buckets; regrowth happens device-side (``dynamic_update_slice`` into padded
buffers, no host round-trip) and the jitted degree step is cached *globally*
per config, so the steady state compiles exactly once per ``(Lcap, Kcap)``
bucket — ``stats["recompiles"]`` counts the compiles a fit actually
triggered, and benchmarks assert it stays at zero once warm.

Engines
-------
* ``engine='oracle'`` — paper-faithful: each candidate is decided by the
  configured convex oracle (AGD / CG / PCG / BPCG), optionally warm-started by
  IHB (CGAVI-IHB / AGDAVI-IHB), optionally re-solved sparsely (WIHB).
* ``engine='fast'``  — beyond-paper: pure closed-form IHB decisions
  (exact unconstrained optima; equals AGDAVI-IHB with an accurate oracle).

The IHB state is slimmed to the engine: only the factor the configured
``inverse_engine`` needs is materialized and updated per candidate (``N`` or
``R``; ``AtA`` only for the convex oracles) — see
:func:`repro.core.ihb.factors_for`.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels import ops as kernel_ops
from . import ihb as ihb_mod
from . import terms as terms_mod
from .oracles import (
    SOLVERS,
    OracleConfig,
    solve_agd,
    solve_bpcg,
    solve_cg,
    solve_pcg,
)
from .ordering import pearson_order

_SOLVER_FNS = {"agd": solve_agd, "cg": solve_cg, "pcg": solve_pcg, "bpcg": solve_bpcg}

# Matmuls at full f32 precision: TPU's default rounds f32 operands to bf16,
# which moves transform outputs by ~1e-2 relative (see PERF.md).
_HIGHEST = jax.lax.Precision.HIGHEST


def _np_dtype(dtype) -> np.dtype:
    """``np.dtype`` for possibly-extension dtype names (``"bfloat16"``):
    plain numpy only understands those once ml_dtypes is registered, which
    routing through ``jnp.dtype`` guarantees."""
    return np.dtype(jnp.dtype(dtype))


@dataclasses.dataclass(frozen=True)
class OAVIConfig:
    psi: float = 0.005
    engine: str = "fast"  # 'fast' | 'oracle'
    solver: OracleConfig = dataclasses.field(default_factory=OracleConfig)
    ihb: bool = True  # warm-start oracle with the closed-form optimum
    wihb: bool = False  # re-solve accepted generators sparsely (BPCGAVI-WIHB)
    inverse_engine: str = "inverse"  # 'inverse' (Thm 4.9) | 'chol' (beyond-paper)
    max_degree: int = 10
    cap_terms: int = 64  # initial |O| capacity bucket; grows device-side
    cap_border: int = 64  # initial border capacity; grows on demand
    dtype: str = "float32"
    ordering: str = "pearson"  # 'pearson' | 'none' | 'reverse_pearson'
    tol_dependent: float = 1e-9  # Schur-complement guard (relative)
    # dispatch of both kernels (Gram and IHB update): 'auto' (Pallas on TPU,
    # jnp elsewhere), 'pallas', 'interpret' (Pallas in interpreter mode —
    # tests), 'jnp' (the pure-jnp references)
    kernel: str = "auto"

    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    def ihb_factors(self) -> Tuple[str, ...]:
        return ihb_mod.factors_for(
            self.engine, self.inverse_engine, self.ihb, self.wihb
        )


class Generator(NamedTuple):
    term: terms_mod.Term  # leading term
    parent_idx: int  # index (into O) of the parent term, term = parent * x_var
    var: int
    coeffs: np.ndarray  # coefficients over O terms (length = |O| at accept time)
    mse: float


@dataclasses.dataclass
class OAVIModel:
    """Output of OAVI: term book for O, generators G, and transform machinery."""

    n: int
    psi: float
    book: terms_mod.TermBook
    generators: List[Generator]
    feature_perm: Optional[np.ndarray]  # Pearson ordering permutation (or None)
    stats: Dict
    dtype: str = "float32"

    @property
    def num_O(self) -> int:
        return len(self.book)

    @property
    def num_G(self) -> int:
        return len(self.generators)

    def term_arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        return (
            np.asarray(self.book.parents, dtype=np.int32),
            np.asarray(self.book.vars, dtype=np.int32),
        )

    def generator_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        k = len(self.generators)
        ell = len(self.book)
        C = np.zeros((ell, k), dtype=_np_dtype(self.dtype))
        gp = np.zeros((k,), dtype=np.int32)
        gv = np.zeros((k,), dtype=np.int32)
        for j, g in enumerate(self.generators):
            C[: len(g.coeffs), j] = g.coeffs
            gp[j] = g.parent_idx
            gv[j] = g.var
        return C, gp, gv

    def evaluate_O(self, Z: jax.Array) -> jax.Array:
        """Evaluation matrix O(Z): (q, |O|) — degree-wavefront evaluation."""
        parents, vars_ = self.term_arrays()
        return evaluate_terms(jnp.asarray(Z, self.dtype), parents, vars_)

    def evaluate_G(self, Z: jax.Array) -> jax.Array:
        """Evaluation matrix G(Z): (q, |G|).  Theorem 4.2 machinery."""
        Z = jnp.asarray(Z, self.dtype)
        if self.feature_perm is not None:
            Z = Z[:, self.feature_perm]
        cols = self.evaluate_O(Z)
        if not self.generators:
            return jnp.zeros((Z.shape[0], 0), self.dtype)
        C, gp, gv = self.generator_arrays()
        lead = cols[:, gp] * Z[:, gv]  # leading-term columns
        return jnp.matmul(cols, jnp.asarray(C), precision=_HIGHEST) + lead

    def mse(self, Z: jax.Array) -> jax.Array:
        """Per-generator MSE over Z."""
        G = self.evaluate_G(Z)
        return jnp.mean(G * G, axis=0)

    # -- VanishingIdealModel protocol (see repro.api) ---------------------

    def transform(self, Z) -> np.ndarray:
        """(FT) for this model alone: ``|G(Z)|`` as (q, |G|) in model dtype."""
        return np.abs(np.asarray(self.evaluate_G(Z)))

    def to_state_dict(self) -> Tuple[Dict[str, np.ndarray], Dict]:
        """Flat array tree + JSON-safe metadata.  The term book and generator
        leading terms are not stored explicitly: both replay from the
        ``(parent, var)`` chains, so the arrays below are the whole model."""
        parents, vars_ = self.term_arrays()
        k = len(self.generators)
        L = len(self.book)
        coeffs = np.zeros((k, L), dtype=_np_dtype(self.dtype))
        lens = np.zeros((k,), np.int32)
        gp = np.zeros((k,), np.int32)
        gv = np.zeros((k,), np.int32)
        mses = np.zeros((k,), np.float64)
        for j, g in enumerate(self.generators):
            coeffs[j, : len(g.coeffs)] = g.coeffs
            lens[j] = len(g.coeffs)
            gp[j] = g.parent_idx
            gv[j] = g.var
            mses[j] = g.mse
        perm = (
            np.asarray(self.feature_perm, np.int32)
            if self.feature_perm is not None
            else np.zeros((0,), np.int32)
        )
        arrays = {
            "book_parents": parents,
            "book_vars": vars_,
            "gen_coeffs": coeffs,
            "gen_lens": lens,
            "gen_parent": gp,
            "gen_var": gv,
            "gen_mse": mses,
            "feature_perm": perm,
        }
        meta = {
            "kind": "oavi",
            "n": int(self.n),
            "psi": float(self.psi),
            "dtype": str(self.dtype),
            "has_perm": self.feature_perm is not None,
            "stats": self.stats,
        }
        return arrays, meta

    @classmethod
    def from_state_dict(cls, arrays: Dict[str, np.ndarray], meta: Dict) -> "OAVIModel":
        n = int(meta["n"])
        dtype = str(meta["dtype"])
        bp = np.asarray(arrays["book_parents"]).astype(np.int64)
        bv = np.asarray(arrays["book_vars"]).astype(np.int64)
        book = terms_mod.TermBook(n=n)
        for i in range(1, bp.shape[0]):
            parent = book.terms[int(bp[i])]
            var = int(bv[i])
            book.append(terms_mod.multiply_by_var(parent, var), parent, var)
        coeffs = np.asarray(arrays["gen_coeffs"]).astype(_np_dtype(dtype))
        lens = np.asarray(arrays["gen_lens"]).astype(np.int64)
        gp = np.asarray(arrays["gen_parent"]).astype(np.int64)
        gv = np.asarray(arrays["gen_var"]).astype(np.int64)
        mses = np.asarray(arrays["gen_mse"]).astype(np.float64)
        generators = []
        for j in range(gp.shape[0]):
            p, v = int(gp[j]), int(gv[j])
            generators.append(
                Generator(
                    term=terms_mod.multiply_by_var(book.terms[p], v),
                    parent_idx=p,
                    var=v,
                    coeffs=coeffs[j, : int(lens[j])].copy(),
                    mse=float(mses[j]),
                )
            )
        perm = (
            np.asarray(arrays["feature_perm"]).astype(np.int64)
            if meta.get("has_perm")
            else None
        )
        return cls(
            n=n,
            psi=float(meta["psi"]),
            book=book,
            generators=generators,
            feature_perm=perm,
            stats=dict(meta.get("stats") or {}),
            dtype=dtype,
        )

    def save(self, path: str) -> str:
        """Atomic save via the checkpoint manifest machinery (repro.api)."""
        from .. import api

        return api.save(self, path)


@partial(jax.jit, donate_argnums=(0,))
def _append_columns(A, B, slots, appended):
    """Scatter appended candidate columns of B into A at their slots."""
    safe_slots = jnp.where(appended, slots, 0)
    contrib = jnp.where(appended[None, :], B, 0.0)
    return A.at[:, safe_slots].add(contrib, mode="drop")


# ---------------------------------------------------------------------------
# Term evaluation: degree-wavefront (serving hot path) + sequential reference
# ---------------------------------------------------------------------------


def evaluate_terms_sequential(
    Z: jax.Array, parents: jax.Array, vars_: jax.Array
) -> jax.Array:
    """Sequential reference: col_i = col_parent * Z[:, var], one term at a
    time (O(|O|) dependent steps).  Works with traced ``parents``/``vars_``;
    kept as the oracle for the wavefront path and for callers inside jit."""
    q = Z.shape[0]
    ell = parents.shape[0]
    cols0 = jnp.zeros((q, ell), Z.dtype).at[:, 0].set(1.0)

    def body(i, cols):
        col = cols[:, parents[i]] * Z[:, vars_[i]]
        return jax.lax.dynamic_update_slice(cols, col[:, None], (0, i))

    return jax.lax.fori_loop(1, ell, body, cols0)


def wavefront_schedule(parents, vars_):
    """Degree-wavefront evaluation plan for a term book.

    A term's parent has *exactly* one degree less (``term = parent * x_var``),
    so all terms of one degree evaluate in a single batched gather+product
    over the previous degree's block — O(max_degree) sequential steps instead
    of O(|O|), and each step only touches two thin blocks.

    Returns ``(waves, perm)``: ``waves[d] = (parent_pos, var)`` with
    ``parent_pos`` indexing into the degree-``d-1`` block, and ``perm`` the
    gather restoring original column order after concatenating the blocks
    (``None`` when the book is already degree-ordered — single-model books).
    """
    parents = np.asarray(parents, np.int64)
    vars_np = np.asarray(vars_, np.int64)
    L = parents.shape[0]
    deg = np.zeros((L,), np.int64)
    for i in range(1, L):
        deg[i] = deg[parents[i]] + 1
    waves = []
    prev_idx = np.zeros((1,), np.int64)  # wave 0: the constant column
    order = [prev_idx]
    for d in range(1, int(deg.max()) + 1 if L > 1 else 1):
        idx = np.nonzero(deg == d)[0]
        pos = np.searchsorted(prev_idx, parents[idx])
        assert np.array_equal(prev_idx[pos], parents[idx]), "parent not at degree d-1"
        waves.append((pos.astype(np.int32), vars_np[idx].astype(np.int32)))
        order.append(idx)
        prev_idx = idx
    order = np.concatenate(order)
    perm = None if np.array_equal(order, np.arange(L)) else np.argsort(order).astype(np.int32)
    return tuple(waves), perm


def apply_wavefronts(Z, waves, perm=None) -> jax.Array:
    """Evaluate a wavefront schedule over ``Z``: one select-matmul + product
    per degree (each reading only the previous degree's block), one concat,
    and — only for fused multi-book plans — one column permutation.

    The column selections are expressed as one-hot matmuls (the same
    gather-as-matmul trick as the gram kernel): exact for any dtype (each
    output sums one value plus hard zeros) at ``HIGHEST`` precision — the
    TPU's default would round the selected values to bf16 — MXU-friendly on
    TPU, and far faster than XLA's scalar gathers on CPU.
    """
    prev = jnp.ones((Z.shape[0], 1), Z.dtype)
    blocks = [prev]
    prev_size = 1
    n = Z.shape[1]
    for pos, var in waves:
        k = pos.shape[0]
        Psel = np.zeros((prev_size, k), np.float32)
        Psel[pos, np.arange(k)] = 1.0
        Vsel = np.zeros((n, k), np.float32)
        Vsel[var, np.arange(k)] = 1.0
        prev = jnp.matmul(prev, jnp.asarray(Psel, Z.dtype), precision=_HIGHEST) * (
            jnp.matmul(Z, jnp.asarray(Vsel, Z.dtype), precision=_HIGHEST)
        )
        blocks.append(prev)
        prev_size = k
    cols = jnp.concatenate(blocks, axis=1) if len(blocks) > 1 else blocks[0]
    if perm is not None:
        cols = jnp.take(cols, jnp.asarray(perm), axis=1)
    return cols


# LRU-bounded: a long-lived process fitting many models must not pin one
# jitted evaluator (closure + compiled executable) per term book forever.
_WAVEFRONT_CACHE: "OrderedDict[Tuple[bytes, bytes], Callable]" = OrderedDict()
_WAVEFRONT_CACHE_SIZE = 64


def make_wavefront_evaluator(parents, vars_) -> Callable[[jax.Array], jax.Array]:
    """Jitted ``Z -> O(Z)`` for one (host-side) term book; cached per book so
    serving loops compile once per model set."""
    parents = np.asarray(parents, np.int32)
    vars_np = np.asarray(vars_, np.int32)
    key = (parents.tobytes(), vars_np.tobytes())
    fn = _WAVEFRONT_CACHE.get(key)
    if fn is None:
        waves, perm = wavefront_schedule(parents, vars_np)

        @jax.jit
        def fn(Z):
            return apply_wavefronts(Z, waves, perm)

        _WAVEFRONT_CACHE[key] = fn
        if len(_WAVEFRONT_CACHE) > _WAVEFRONT_CACHE_SIZE:
            _WAVEFRONT_CACHE.popitem(last=False)
    else:
        _WAVEFRONT_CACHE.move_to_end(key)
    return fn


def evaluate_terms(Z: jax.Array, parents, vars_) -> jax.Array:
    """Evaluate all O terms over Z incrementally: col_i = col_parent * Z[:, var].

    With concrete (host-side) ``parents``/``vars_`` — the serving case — the
    degree-wavefront evaluator runs all terms of a degree in one batched
    step.  Traced index arrays fall back to the sequential loop.
    """
    try:
        parents_np = np.asarray(parents)
        vars_np = np.asarray(vars_)
    except jax.errors.TracerArrayConversionError:  # traced indices (inside a jit)
        return evaluate_terms_sequential(Z, parents, vars_)
    return make_wavefront_evaluator(parents_np, vars_np)(jnp.asarray(Z))


# ---------------------------------------------------------------------------
# The jitted degree step
# ---------------------------------------------------------------------------


class _LoopState(NamedTuple):
    ihb: ihb_mod.IHBState
    ell: jax.Array  # active |O|
    ihb_live: jax.Array  # bool: IHB still enabled (INF guard, §4.4.3)
    accepted: jax.Array  # (K,) bool
    slots: jax.Array  # (K,) slot index for appended candidates
    coeffs: jax.Array  # (K, L)
    mses: jax.Array  # (K,)
    iters: jax.Array  # (K,) solver iterations (0 for pure closed-form)


def _kernel_kwargs(cfg: OAVIConfig) -> Dict:
    return {
        "auto": {},
        "pallas": {"use_pallas": True},
        "interpret": {"interpret": True},
        "jnp": {"use_pallas": False},
    }[cfg.kernel]


def _make_stats_degree_step(cfg: OAVIConfig, reduce_fn=None):
    """Build the *statistics-only* degree step: every accept/reject decision
    of one degree from the raw Gram sufficient statistics alone — the
    evaluation matrix A never enters.  This is the piece the out-of-core fit
    (:mod:`repro.streaming.fit`) runs after its chunk accumulator has reduced
    A away; the in-memory :func:`_make_degree_step` wraps it with the Gram
    computation and the A column scatter.  ``reduce_fn`` (e.g. a psum) is
    applied to the raw Gram quantities; None means single-device.

    Oracle/WIHB configs run the ``while_loop`` solvers of
    :mod:`repro.core.oracles`, which stop the moment a certificate fires;
    under ``vmap`` (the class-batched / streaming-batched paths) a solve
    runs until its slowest lane stops, and every lane keeps the bits of its
    own single solve."""

    solver = SOLVERS[cfg.solver.name]
    wihb_solver = solve_bpcg
    use_chol = cfg.inverse_engine == "chol"
    engine_oracle = cfg.engine == "oracle"
    # closed-form optimum needed: always for 'fast', as a warm start otherwise
    need_closed_form = (not engine_oracle) or cfg.ihb
    rfn = reduce_fn if reduce_fn is not None else (lambda x: x)
    kernel_kw = _kernel_kwargs(cfg)

    def stats_step(QL_raw, C_raw, state: ihb_mod.IHBState, ell0, valid, m_total):
        dtype = cfg.jax_dtype()
        Lcap = QL_raw.shape[0]
        K = valid.shape[0]
        psi = jnp.asarray(cfg.psi, dtype)
        # All Gram quantities are normalized by m (work with Abar = A/sqrt(m)):
        # entries stay in [0,1] (X in [0,1]^n), which keeps fp32 well behaved
        # for m in the millions, and MSE(g) = btb + q^T y exactly.
        inv_m = jnp.asarray(1.0 / m_total, dtype)
        one = jnp.asarray(1.0, dtype)

        QL = (rfn(QL_raw) * inv_m).astype(dtype)  # (L, K)
        C = (rfn(C_raw) * inv_m).astype(dtype)  # (K, K)

        # ---- (3): sequential acceptance over candidates ---------------
        def body(a, st: _LoopState) -> _LoopState:
            q = QL[:, a]
            # correction for columns appended earlier in this degree:
            appended_before = (jnp.arange(K) < a) & (~st.accepted) & (st.slots < Lcap) & valid
            safe_slots = jnp.where(appended_before, st.slots, 0)
            q = q.at[safe_slots].add(jnp.where(appended_before, C[:, a], 0.0), mode="drop")
            btb = C[a, a]

            mask = jnp.arange(Lcap) < st.ell
            u = None  # N q: shared by the warm start and the Thm 4.9 update
            if need_closed_form:
                if use_chol:
                    y0 = ihb_mod.closed_form_cholesky(st.ihb, q)
                else:
                    u = ihb_mod.inverse_times(st.ihb, q)
                    y0 = -u
                y0 = jnp.where(mask, y0, 0.0)

            if not engine_oracle:
                # sum(q * y0), not q @ y0: the elementwise+reduce lowering is
                # bit-stable under vmap (class-batched fit); a fused dot is not
                mse0 = btb + jnp.sum(q * y0)
                y, mse_final, it = y0, mse0, jnp.asarray(0, jnp.int32)
                ihb_live = st.ihb_live
            else:
                if cfg.ihb:
                    # (INF) guard: if the warm start leaves the l1 ball, stop
                    # using IHB from now on (paper §4.4.3, second approach).
                    # Only *valid* candidates can trip it — padded lanes solve
                    # garbage Gram columns, and their verdicts must not leak
                    # into real candidates (padding differs across the
                    # sequential / class-batched paths).
                    feasible = jnp.sum(jnp.abs(y0)) <= (cfg.solver.tau - 1.0)
                    use_warm = st.ihb_live & feasible
                    ihb_live = st.ihb_live & (feasible | ~valid[a])
                    warm = jnp.where(use_warm, y0, 0.0)
                else:
                    ihb_live = st.ihb_live
                    warm = jnp.zeros((Lcap,), dtype)
                res = solver(st.ihb.AtA, q, btb, one, mask, psi, cfg.solver, warm)
                y, mse_final, it = res.y, res.f, res.iters

            accept = (mse_final <= psi) & valid[a]

            if cfg.wihb:
                # re-solve accepted generators sparsely from a cold start
                # (under vmap the cond computes both branches and selects)
                def resolve():
                    res = wihb_solver(st.ihb.AtA, q, btb, one, mask, psi, cfg.solver, None)
                    ok = res.f <= psi
                    return jnp.where(ok, res.y, y), jnp.where(ok, res.f, mse_final), res.iters

                y, mse_final, extra = jax.lax.cond(
                    accept, resolve, lambda: (y, mse_final, jnp.asarray(0, jnp.int32))
                )
                it = it + extra

            # On reject: append column to O (slot = ell), update Gram/inverse.
            do_append = (~accept) & valid[a]

            def appended(st_in: _LoopState):
                new_ihb = ihb_mod.append_column(
                    st_in.ihb, q, btb, st_in.ell, u=u, kernel_kw=kernel_kw
                )
                return st_in._replace(
                    ihb=new_ihb,
                    ell=st_in.ell + 1,
                    slots=st_in.slots.at[a].set(st_in.ell),
                )

            st = jax.lax.cond(do_append, appended, lambda s: s, st)
            st = st._replace(
                ihb_live=ihb_live,
                accepted=st.accepted.at[a].set(accept),
                coeffs=st.coeffs.at[a].set(jnp.where(accept, y, 0.0)),
                mses=st.mses.at[a].set(mse_final),
                iters=st.iters.at[a].set(it),
            )
            return st

        st0 = _LoopState(
            ihb=state,
            ell=ell0,
            ihb_live=jnp.asarray(True),
            accepted=jnp.zeros((K,), bool),
            slots=jnp.full((K,), Lcap, jnp.int32),
            coeffs=jnp.zeros((K, Lcap), dtype),
            mses=jnp.zeros((K,), dtype),
            iters=jnp.zeros((K,), jnp.int32),
        )
        return jax.lax.fori_loop(0, K, body, st0)

    return stats_step


def _make_degree_step(cfg: OAVIConfig, reduce_fn=None):
    """Build the jitted in-memory degree step: the fused Gram computation,
    the statistics-only acceptance loop (:func:`_make_stats_degree_step`),
    and the scatter of appended candidate columns into A."""

    stats_step = _make_stats_degree_step(cfg, reduce_fn)
    gram_kw = _kernel_kwargs(cfg)

    def degree_step(A, X, state: ihb_mod.IHBState, ell0, parents, vars_, valid, m_total):
        Lcap = A.shape[1]
        # ---- (1)+(2): all O(m) work, in one fused kernel dispatch ------
        # (Pallas on TPU: border eval + both Grams in a single VMEM sweep;
        # bit-identical gather+matmul fallback elsewhere.)  The reduction is
        # the canonical GRAM_BLOCK-row blocked order, so the streaming fit's
        # chunk accumulator lands on the same bits (repro.streaming.fit).
        QL_raw, C_raw = kernel_ops.gram_accumulate(A, X, parents, vars_, **gram_kw)
        # candidate columns, needed again to scatter appended ones into A
        B = jnp.take(A, parents, axis=1) * jnp.take(X, vars_, axis=1)

        st = stats_step(QL_raw, C_raw, state, ell0, valid, m_total)

        # ---- write appended columns into A -----------------------------
        appended = (~st.accepted) & valid & (st.slots < Lcap)
        A = _append_columns(A, B, st.slots, appended)
        return A, st

    return degree_step


# ---------------------------------------------------------------------------
# Degree-step cache: one jitted step per config, one compile per shape bucket
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _StepEntry:
    fn: Callable
    seen: set  # shape signatures already traced by ``fn``


_DEGREE_STEP_CACHE: Dict = {}


def degree_step_entry(
    config: OAVIConfig,
    backend_key=None,
    jitted_builder: Optional[Callable] = None,
    factory: Optional[Callable] = None,
) -> _StepEntry:
    """Jitted degree step, cached globally per ``(config, backend_key)``.

    ``jax.jit``'s own trace cache buckets on argument shapes; ``seen``
    mirrors it host-side so fits can count the compiles they actually
    trigger (``stats["recompiles"]``).  ``jitted_builder`` overrides how the
    cached step is built on a miss (the sharded backend).  A custom
    ``factory`` (test hook: zero-arg, returns an unjitted step) gets a fresh
    uncached entry.
    """
    if factory is not None:
        return _StepEntry(fn=jax.jit(factory()), seen=set())
    key = (config, backend_key)
    entry = _DEGREE_STEP_CACHE.get(key)
    if entry is None:
        build = jitted_builder or (lambda: jax.jit(_make_degree_step(config)))
        entry = _StepEntry(fn=build(), seen=set())
        _DEGREE_STEP_CACHE[key] = entry
    return entry


def pow2_bucket(x: int) -> int:
    """Smallest power of two >= x (shape bucketing for Lcap / Kcap / m_cap)."""
    return 1 << max(int(x) - 1, 1).bit_length() if x > 2 else 2


def class_batchable(config: OAVIConfig) -> bool:
    """Whether a config is eligible for the class-batched (vmapped) fit path
    (:mod:`repro.core.class_batch`).

    The batched path guarantees bit-exactness against the sequential fit at
    matched capacity, which restricts it to configurations whose degree step
    is built from vmap-bit-stable primitives (batched matmuls/matvecs match
    their per-slice counterparts on every backend we test).  Every engine
    qualifies, the convex oracles included (:mod:`repro.core.oracles`):
    under ``vmap`` their ``while_loop`` runs until the slowest lane stops,
    and stopped lanes ride as bitwise no-ops, which reproduces the per-class
    results bit-for-bit.

    The one remaining exclusion is ``inverse_engine='chol'``: batched
    triangular solves do not reduce in the same order as their
    single-instance lowering, breaking bit-exactness.
    """
    return config.inverse_engine == "inverse"


# Memory accounting moved to repro.obs.device (PR 10) — these aliases keep
# the long-standing call sites and benchmark imports working.  The device
# module adds the registry gauges and the trace-counter memory timeline on
# top of the same sampling.
device_memory_stats = obs.device.device_memory_stats
live_buffer_bytes = obs.device.live_buffer_bytes


def sample_memory_stats(stats: Dict) -> None:
    """Record the current memory high-water marks into a fit ``stats`` dict:
    ``peak_bytes`` from the device allocator where available (gracefully
    absent otherwise) and ``live_bytes_peak`` from live-array accounting.
    Fit loops call this per degree and once at finalize.

    ``peak_bytes`` is the allocator's *process-lifetime* high-water mark —
    it cannot be reset, so a fit that stays under an earlier fit's peak
    inherits it (compare against ``peak_bytes_start`` from
    :func:`init_fit_stats` to bound this fit's contribution).
    ``live_bytes_peak`` is sampled per fit and is the per-fit comparable
    quantity the memory benchmarks prefer.  Delegates to
    :func:`repro.obs.device.sample_memory`, which also refreshes the
    ``device.*`` gauges and appends the trace memory-timeline sample."""
    obs.device.sample_memory(stats)


def init_fit_stats(m: int, n: int, **extra) -> Dict:
    """Common ``stats`` skeleton shared by the local, sharded, class-batched
    and streaming fit loops."""
    stats = {
        "border_sizes": [],
        "solver_iters": [],
        "degrees": [],
        "degree_times": [],
        "recompiles": 0,
        "regrowths": 0,
        # XLA backend-compile seconds attributed to this fit (repro.obs.device)
        "compile_seconds": 0.0,
        "time_total": 0.0,
        "m": m,
        "n": n,
    }
    peak = device_memory_stats().get("peak_bytes_in_use")
    if peak is not None:
        stats["peak_bytes_start"] = int(peak)
    stats.update(extra)
    return stats


class _DegreeScope:
    """One degree step's timing window (see :class:`FitScope.degree`)."""

    __slots__ = ("_scope", "_span", "_t0")

    def __init__(self, scope: "FitScope", span) -> None:
        self._scope = scope
        self._span = span

    def __enter__(self) -> "_DegreeScope":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        self._span.__exit__(exc_type, exc, tb)
        scope = self._scope
        dur = t1 - self._t0
        if scope._t_first_degree is None:
            scope._t_first_degree = self._t0
        scope._t_last_degree_end = t1
        scope._time_degrees += dur
        scope.stats["degree_times"].append(round(dur, 6))
        sample_memory_stats(scope.stats)


class FitScope:
    """Instrumentation shared by every fit loop (local, sharded,
    class-batched, streaming, online).

    Owns the *timing contract* for fit ``stats`` — defined here once so the
    loops can no longer disagree on what ``time_total`` covers (asserted by
    ``tests/test_obs.py``)::

        time_total == time_setup + time_degrees + time_finalize
                      + time_unattributed          # exact, by construction

    * ``time_total``    wall time from scope entry to :meth:`finalize`.
    * ``time_setup``    entry -> first degree step: feature ordering, initial
      buffers, the first border (for the streaming fit this includes the
      Pearson moment pass when ordering is enabled).
    * ``time_degrees``  unrounded sum of the per-degree segments.  Each
      segment runs from the degree step's dispatch to the host sync of its
      outputs, so it *includes* any jit compile the step triggered —
      ``sum(stats["degree_times"])`` equals it up to the 6-decimal rounding
      of the public list.
    * ``time_finalize`` last degree's end -> :meth:`finalize` (final host
      bookkeeping and model assembly).
    * ``time_unattributed`` the residual: host combinatorics between degree
      steps (border construction, accept/reject collection).

    Timing itself is always on (two clock reads per degree); the global obs
    recorder sees the same segments as spans/events only when
    :func:`repro.obs.enabled` — and enabling it never changes what the fit
    computes (bit-identity asserted by ``benchmarks/bench_obs.py``).
    """

    def __init__(self, stats: Dict, backend: str = "local", name: str = "fit") -> None:
        self.stats = stats
        self.backend = backend
        attrs = {k: stats[k] for k in ("m", "n") if stats.get(k) is not None}
        self._span = obs.span(name, backend=backend, **attrs)
        self._t_start = time.perf_counter()
        self._t_first_degree: Optional[float] = None
        self._t_last_degree_end: Optional[float] = None
        self._time_degrees = 0.0
        self._timing: Optional[Dict] = None
        # XLA compile attribution window: always-on (reading the listener's
        # accumulator never touches numerics or the device)
        self._compile0 = obs.device.compile_snapshot()

    def __enter__(self) -> "FitScope":
        self._span.__enter__()
        # env-gated jax.profiler window (OBS_JAX_PROFILE=<dir>): the whole
        # fit in one device-timeline capture, interleaved with obs spans
        self._profile = obs.device.profile_window(f"fit/{self.backend}")
        self._profile.__enter__()
        self._t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._profile.__exit__(exc_type, exc, tb)
        self._span.__exit__(exc_type, exc, tb)

    def degree(self, d: int, **attrs) -> _DegreeScope:
        """Context manager timing one degree step.  On exit it appends the
        (rounded) segment to ``stats["degree_times"]``, accumulates the
        unrounded sum for the timing contract, and samples memory."""
        return _DegreeScope(
            self, obs.span("fit/degree", d=d, backend=self.backend, **attrs)
        )

    def note_signature(self, seen: set, sig, kind: str = "fit/compile") -> bool:
        """Count a compile against this fit iff ``sig`` is new to the jitted
        step's host-side trace-cache mirror; emits the compile event the
        degree-step cache owes the trace."""
        if sig in seen:
            return False
        seen.add(sig)
        self.stats["recompiles"] += 1
        obs.registry().counter("fit.recompiles", backend=self.backend).inc()
        obs.event(kind, backend=self.backend, signature=str(sig))
        return True

    def regrowth(self, Lcap: int) -> None:
        self.stats["regrowths"] += 1
        obs.registry().counter("fit.regrowths", backend=self.backend).inc()
        obs.event("fit/regrowth", backend=self.backend, Lcap=int(Lcap))

    def timing_fields(self) -> Dict:
        """The timing-contract fields, computed once (shared by every class
        of a batched fit so their stats agree to the bit)."""
        if self._timing is None:
            t_end = time.perf_counter()
            total = t_end - self._t_start
            if self._t_first_degree is None:
                setup, degrees, fin = total, 0.0, 0.0
            else:
                setup = self._t_first_degree - self._t_start
                degrees = self._time_degrees
                fin = t_end - self._t_last_degree_end
            self._timing = {
                "time_total": total,
                "time_setup": setup,
                "time_degrees": degrees,
                "time_finalize": fin,
                "time_unattributed": total - setup - degrees - fin,
            }
        return self._timing

    def finalize(
        self,
        book: terms_mod.TermBook,
        generators: List[Generator],
        Lcap: int,
        config: OAVIConfig,
        stats: Optional[Dict] = None,
    ) -> Dict:
        """Fill the summary + timing fields every fit loop reports."""
        stats = self.stats if stats is None else stats
        sample_memory_stats(stats)
        stats.update(self.timing_fields())
        s1, c1 = obs.device.compile_snapshot()
        stats["compile_seconds"] = round(s1 - self._compile0[0], 6)
        stats["xla_compiles"] = c1 - self._compile0[1]
        stats["num_G"] = len(generators)
        stats["num_O"] = len(book)
        stats["G_plus_O"] = len(generators) + len(book)
        stats["Lcap_final"] = int(Lcap)
        stats["thm43_bound"] = terms_mod.theorem_4_3_size_bound(config.psi, book.n)
        obs.registry().histogram("fit.seconds", backend=self.backend).observe(
            stats["time_total"]
        )
        return stats


def border_index_arrays(book: terms_mod.TermBook, border, Kcap: int):
    """Padded (parents, vars, valid) host arrays for one degree's border."""
    parents = np.zeros((Kcap,), np.int32)
    vars_ = np.zeros((Kcap,), np.int32)
    valid = np.zeros((Kcap,), bool)
    for i, (term, parent, j) in enumerate(border):
        parents[i] = book.index[parent]
        vars_[i] = j
        valid[i] = True
    return parents, vars_, valid


def collect_degree(book, border, accepted, mses, coeffs, generators) -> int:
    """Host-side bookkeeping after a degree step: accepted candidates become
    generators, rejected ones extend the term book.  Returns the new |O|."""
    for i, (term, parent, j) in enumerate(border):
        if accepted[i]:
            ell_at = len(book)
            generators.append(
                Generator(
                    term=term,
                    parent_idx=book.index[parent],
                    var=j,
                    coeffs=coeffs[i, :ell_at].copy(),
                    mse=float(mses[i]),
                )
            )
        else:
            book.append(term, parent, j)
    return len(book)


def fit(
    X,
    config: OAVIConfig = OAVIConfig(),
    _degree_step_factory=None,
) -> OAVIModel:
    """Run OAVI on ``X`` (m, n) in [0,1]^n.  Returns the fitted model."""
    dtype = config.jax_dtype()
    X = np.asarray(X)
    m, n = X.shape
    stats = init_fit_stats(m, n)

    with FitScope(stats, backend="local") as scope:
        with obs.span("fit/prepare"):
            perm = None
            if config.ordering in ("pearson", "reverse_pearson"):
                perm = pearson_order(X, reverse=(config.ordering == "reverse_pearson"))
                X = X[:, perm]

            Xd = jnp.asarray(X, dtype)
            book = terms_mod.TermBook(n=n)
            generators: List[Generator] = []

            Lcap = pow2_bucket(config.cap_terms)
            A = jnp.zeros((m, Lcap), dtype).at[:, 0].set(1.0)
            # normalized Gram convention: AtA[0,0] = ||1||^2 / m = 1
            state = ihb_mod.init_state(
                Lcap, jnp.asarray(1.0, dtype), dtype, factors=config.ihb_factors()
            )
            ell = 1

            entry = degree_step_entry(config, factory=_degree_step_factory)
            m_total = jnp.asarray(float(m), dtype)

        d = 0
        while True:
            d += 1
            if d > config.max_degree:
                stats["termination"] = f"max_degree={config.max_degree}"
                break
            with obs.span("fit/border"):
                border = book.border(d)
                if not border:
                    stats["termination"] = "empty_border"
                    break
                K = len(border)
                stats["border_sizes"].append(K)
                stats["degrees"].append(d)

                # capacity management: device-side regrowth into the next pow2 bucket
                while ell + K > Lcap:
                    Lcap *= 2
                    scope.regrowth(Lcap)
                    A = jax.lax.dynamic_update_slice(
                        jnp.zeros((m, Lcap), dtype), A, (0, 0)
                    )
                    state = ihb_mod.grow_state(state, Lcap)

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
                sig = (m, n, Lcap, Kcap, str(dtype))
                scope.note_signature(entry.seen, sig)

            with scope.degree(d, K=K):
                A, st = entry.fn(*step_args)
                state = st.ihb
                accepted = np.asarray(st.accepted)
                mses = np.asarray(st.mses)
                coeffs = np.asarray(st.coeffs)
                iters = np.asarray(st.iters)

            with obs.span("fit/collect"):
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
