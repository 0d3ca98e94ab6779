"""TransformEngine: compiled, shape-bucketed, optionally sharded (FT) serving.

One engine owns one model set (the per-class models of a classifier, or a
single model) and the fused evaluation plan built from it by
:func:`repro.api.plan_constants` — the same hoisted trace constants the
direct :func:`repro.api.feature_transform` path uses, so both paths are
bit-identical at matched dtype.

Request shapes never recompile: a query of ``q`` rows is zero-padded up to a
**pow2 row bucket** (clamped to ``[min_bucket, max_bucket]`` and rounded up
to the data-shard count), mirroring the zero-recompile ``(Lcap, Kcap)``
capacity buckets of the fit path.  Every row of the fused transform is
independent (the whole evaluation is row-parallel matmuls with a fixed
contraction order), so padding rows changes nothing about real rows and the
sliced result is bit-identical to evaluating at the exact shape.

Sharded execution reuses :mod:`repro.core.distributed`'s mesh helpers: rows
are data-parallel over the mesh's ``data_axes`` (``shard_map`` with the same
row spec as the distributed fit), plan constants are replicated (closed
over), and no collectives are needed — the transform is embarrassingly
row-parallel, so multi-host serving scales linearly in devices.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.distributed import (
    data_spec,
    num_data_shards,
)
from ..core.oavi import pow2_bucket
from ..resilience import chaos


class UnsupportedModelError(TypeError):
    """The model set has no fused term-book plan (e.g. VCA) — serve those
    through the legacy per-model loop instead."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Row-bucket policy of a :class:`TransformEngine`.

    ``min_bucket`` bounds the padding waste of tiny requests from below
    (every request costs at least one ``min_bucket``-row device call);
    ``max_bucket`` bounds device memory from above — larger queries stream
    through in full ``max_bucket`` chunks (which are already-warm buckets,
    so chunking never recompiles either).
    """

    min_bucket: int = 64
    max_bucket: int = 16_384  # larger requests chunk through warm buckets

    def __post_init__(self):
        if self.min_bucket < 1 or self.max_bucket < self.min_bucket:
            raise ValueError(
                f"need 1 <= min_bucket <= max_bucket, got "
                f"({self.min_bucket}, {self.max_bucket})"
            )


class TransformEngine:
    """Serve the fused feature transform of one model set.

    Parameters
    ----------
    models : the per-class model set (term-book models only — OAVI / ABM).
    mesh : optional ``jax.sharding.Mesh``; when given, every device call is
        ``shard_map``-sharded with rows data-parallel over ``data_axes`` and
        plan constants replicated.  ``mesh=None`` runs locally.
    data_axes : mesh axes the row dimension is sharded over.
    config : row-bucket policy (:class:`EngineConfig`).
    """

    def __init__(
        self,
        models: Sequence,
        *,
        mesh=None,
        data_axes: Sequence[str] = ("data",),
        config: EngineConfig = EngineConfig(),
    ):
        from .. import api

        self.models: Tuple = tuple(models)
        self._model_key = tuple(id(m) for m in self.models)
        plan = api._fuse(self.models)
        if plan is None:
            raise UnsupportedModelError(
                "TransformEngine needs term-book models (OAVI/ABM); got a "
                "model set with no fused plan (e.g. VCA or mixed dtypes) — "
                "use repro.api.feature_transform's per-model fallback"
            )
        self.plan = plan
        self.consts = api.plan_constants(plan)
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.config = config
        self.shards = 1 if mesh is None else num_data_shards(mesh, self.data_axes)
        # every bucket must split evenly over the data shards AND leave every
        # shard >= 2 rows: a 1-row local shard hits XLA's single-row gemv
        # lowering, whose accumulation order differs from the gemm path and
        # would break bit-identity with the local/direct evaluation
        self.min_bucket = self._round_to_shards(
            max(pow2_bucket(config.min_bucket), 2 * self.shards)
        )
        self.max_bucket = self._round_to_shards(
            max(pow2_bucket(config.max_bucket), self.min_bucket)
        )
        self._fn = self._build_fn()
        self._seen_buckets: set = set()
        self._lock = threading.Lock()
        self.backend = "local" if mesh is None else "sharded"
        # obs metric primitives (always live — ``stats`` is a view over them;
        # the span/trace layer is what OBS_ENABLED gates)
        self._requests = obs.Counter()
        self._rows = obs.Counter()
        self._device_calls = obs.Counter()
        self._padded_rows = obs.Counter()
        self._recompiles = obs.Counter()
        self._warmup_compiles = obs.Counter()
        self._bucket_calls: Dict[int, obs.Counter] = {}
        # per-engine request latency sketch (p50/p99/p999 via stats view);
        # also folded into the process-global serve SLO histogram by label
        self.latency = obs.Histogram()
        self._slo = obs.registry().histogram(
            "serve.transform_seconds", backend=self.backend
        )
        # XLA backend-compile seconds attributed to this engine's
        # warmup/first-call compiles
        self._compile_seconds = 0.0

    @property
    def stats(self) -> Dict:
        """Point-in-time counter view (same keys as the historical dict)."""
        return {
            "requests": self._requests.value,
            "rows": self._rows.value,
            "device_calls": self._device_calls.value,
            "padded_rows": self._padded_rows.value,
            "recompiles": self._recompiles.value,
            "warmup_compiles": self._warmup_compiles.value,
            "buckets": {b: c.value for b, c in sorted(self._bucket_calls.items())},
            "latency": self.latency.summary(),
            "compile_seconds": round(self._compile_seconds, 6),
        }

    # -- plan / shape machinery -------------------------------------------

    def _round_to_shards(self, b: int) -> int:
        return ((b + self.shards - 1) // self.shards) * self.shards

    def _build_fn(self):
        consts = self.consts
        from .. import api

        def eval_fn(Z):
            return api.eval_with_constants(consts, Z)

        if self.mesh is None:
            return jax.jit(eval_fn)
        dspec = data_spec(self.data_axes)
        sharded = jax.shard_map(
            eval_fn,
            mesh=self.mesh,
            in_specs=(dspec,),
            out_specs=dspec,
            check_vma=False,
        )
        return jax.jit(sharded)

    def matches(self, models: Sequence) -> bool:
        """True when this engine serves exactly ``models`` (by identity)."""
        return tuple(id(m) for m in models) == self._model_key

    def bucket_for(self, q: int) -> int:
        """Row bucket a ``q``-row request pads to (pow2, clamped, shard-even)."""
        b = min(max(pow2_bucket(max(q, 1)), self.min_bucket), self.max_bucket)
        return self._round_to_shards(b)

    def buckets(self) -> Tuple[int, ...]:
        """Every bucket this engine can dispatch (smallest to largest)."""
        out = []
        b = self.min_bucket
        while b < self.max_bucket:
            out.append(b)
            b = self._round_to_shards(pow2_bucket(b + 1))
        out.append(self.max_bucket)
        return tuple(out)

    # -- execution ---------------------------------------------------------

    def warmup(self, max_rows: Optional[int] = None) -> int:
        """Trace-and-compile every bucket up to ``max_rows`` (default: all).

        Returns the number of compiles triggered.  After a full warmup a
        request trace of any shape mix runs with ``stats["recompiles"] == 0``.
        """
        top = self.max_bucket if max_rows is None else self.bucket_for(max_rows)
        compiled = 0
        with obs.device.profile_window("serve/warmup"):
            for b in self.buckets():
                if b > top:
                    break
                with self._lock:
                    if b in self._seen_buckets:
                        continue
                    self._seen_buckets.add(b)
                Zb = np.zeros((b, self.consts.n), self.plan.dtype)
                with obs.span(
                    "serve/warmup_compile", bucket=b, backend=self.backend
                ), obs.device.CompileWindow() as cw:
                    jax.block_until_ready(self._fn(jnp.asarray(Zb)))
                with self._lock:
                    self._compile_seconds += cw.seconds
                compiled += 1
        self._warmup_compiles.inc(compiled)
        return compiled

    def _dispatch(self, Zp: np.ndarray) -> np.ndarray:
        """One padded device call at a bucket shape; updates compile stats."""
        b = Zp.shape[0]
        fresh = False
        with self._lock:
            if b not in self._seen_buckets:
                self._seen_buckets.add(b)
                self._recompiles.inc()
                fresh = True
                obs.event("serve/recompile", bucket=b, backend=self.backend)
            bucket = self._bucket_calls.get(b)
            if bucket is None:
                bucket = self._bucket_calls.setdefault(b, obs.Counter())
        self._device_calls.inc()
        bucket.inc()
        if not fresh:
            return np.asarray(self._fn(jnp.asarray(Zp)))
        # cold bucket outside warmup: attribute the XLA compile to the engine
        with obs.device.CompileWindow() as cw:
            out = np.asarray(self._fn(jnp.asarray(Zp)))
        with self._lock:
            self._compile_seconds += cw.seconds
        return out

    def transform(self, Z) -> np.ndarray:
        """(FT) features for one request: (q, num_features) in plan dtype.

        Bit-identical to ``api.feature_transform(self.models, Z)`` at the
        plan dtype for any q; rows beyond ``max_bucket`` stream through in
        full already-warm chunks.
        """
        Z = np.asarray(Z)
        if Z.ndim != 2 or Z.shape[1] != self.consts.n:
            raise ValueError(
                f"expected (q, {self.consts.n}) queries, got {Z.shape}"
            )
        # chaos hook: transient/poison/hang faults fire HERE, the device-call
        # boundary — the batcher's retry and bisection paths see exactly what
        # a failing accelerator call would look like (no-op without a plan)
        chaos.fire("engine.transform", Z=Z)
        q = Z.shape[0]
        self._requests.inc()
        self._rows.inc(q)
        out_dtype = self.plan.dtype
        if q == 0 or self.consts.num_features == 0:
            return np.zeros((q, self.consts.num_features), out_dtype)
        t0 = time.perf_counter()
        with obs.span("serve/transform", rows=q, backend=self.backend):
            Zd = Z.astype(self.plan.dtype, copy=False)
            out = np.empty((q, self.consts.num_features), out_dtype)
            start = 0
            while start < q:
                stop = min(start + self.max_bucket, q)
                chunk = Zd[start:stop]
                b = self.bucket_for(chunk.shape[0])
                if chunk.shape[0] < b:
                    Zp = np.zeros((b, self.consts.n), self.plan.dtype)
                    Zp[: chunk.shape[0]] = chunk
                    self._padded_rows.inc(b - chunk.shape[0])
                else:
                    Zp = chunk
                out[start:stop] = self._dispatch(Zp)[: chunk.shape[0]]
                start = stop
        dur = time.perf_counter() - t0
        self.latency.observe(dur)
        self._slo.observe(dur)
        return out

    def __repr__(self) -> str:
        where = (
            "local"
            if self.mesh is None
            else f"sharded(shards={self.shards}, axes={self.data_axes})"
        )
        return (
            f"TransformEngine(models={len(self.models)}, "
            f"features={self.consts.num_features}, {where}, "
            f"buckets=[{self.min_bucket}..{self.max_bucket}])"
        )
