"""Algorithm 2: per-class generator construction -> (FT) -> linear SVM.

The paper's end-to-end classification pipeline.  ``method`` is a
:mod:`repro.api` spec string (``"oavi:cgavi-ihb"``, ``"abm"``, ``"vca"``,
...; bare OAVI variant names like ``"fast"`` keep working).  Generator
construction is dispatched through :func:`repro.api.fit_classes` — with
``class_batch="auto"`` (default) eligible per-class OAVI fits are grouped
into shared pow2 row buckets and driven through ONE vmapped jitted degree
step (:mod:`repro.core.class_batch`; bit-exact vs sequential at matched
capacity) — oracle/WIHB configs run their masked fixed-schedule solvers
under the vmap, stragglers fold into their cheapest warm bucket, and only
the Cholesky engine falls back to sequential fits — the feature transform
runs through the fused
:func:`repro.api.feature_transform`, and the features are classified by the
l1 squared-hinge :class:`~repro.core.svm.LinearSVM`.

A fitted pipeline serializes whole (scaler + per-class models + SVM head)
through the checkpoint manifest machinery (``save`` / ``load``), and
``attach_engine`` routes ``transform`` / ``predict`` through the serving
:class:`~repro.serving.engine.TransformEngine` (shape-bucketed, optionally
sharded; per-model fallback kept for VCA).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import obs
from .svm import LinearSVM, LinearSVMConfig
from .transform import MinMaxScaler

CLASSIFIER_FORMAT = "repro.vanishing_ideal_classifier.v1"


def __getattr__(name: str):
    # Deprecated alias: the canonical variant table lives in repro.api.
    if name == "VARIANTS":
        from .. import api

        return api.OAVI_VARIANTS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def oavi_config_for(variant: str, psi: float, **kw):
    """Deprecated alias for :func:`repro.api.oavi_config_for`."""
    from .. import api

    return api.oavi_config_for(variant, psi, **kw)


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    method: str = "fast"  # repro.api method spec (or bare OAVI variant name)
    psi: float = 0.005
    svm: LinearSVMConfig = dataclasses.field(default_factory=LinearSVMConfig)
    oavi_kw: Optional[Dict] = None  # forwarded to the method config
    backend: str = "auto"  # repro.api backend: 'auto' | 'local' | 'sharded'
    mesh: Optional[Any] = None  # jax Mesh for the sharded backend
    batch_size: Optional[int] = None  # fused-transform chunking (rows)
    # 'auto': batch eligible per-class OAVI fits through one vmapped degree
    # step, grouped into shared pow2 row buckets with stragglers folded into
    # their cheapest warm bucket (repro.core.class_batch.plan_class_groups);
    # oracle/WIHB configs use the masked fixed-schedule solvers, only the
    # chol engine falls back to sequential.  'off': always sequential.
    class_batch: str = "auto"
    # out-of-core generator construction: when set, each per-class OAVI fit
    # streams through repro.streaming.fit in chunk_rows-row chunks instead of
    # materializing its evaluation matrix (bit-exact at matched capacity;
    # takes precedence over class_batch).  None: in-memory fits.
    chunk_rows: Optional[int] = None
    # incremental fitting: capture each per-class fit's persisted Gram state
    # (repro.online.FitState, stored on clf.fit_states in class order) so the
    # per-class models can later be refreshed with repro.api.update when data
    # arrives.  Requires chunk_rows (the streaming fit path) and an OAVI
    # method; forces sequential per-class fits (states are per-class).
    capture_fit_state: bool = False


class VanishingIdealClassifier:
    """Fit per-class generators, transform, train a linear SVM (Algorithm 2)."""

    def __init__(self, config: PipelineConfig = PipelineConfig()):
        self.config = config
        # thread the model dtype through the scaler so float32 models are not
        # silently fed float64 inputs
        self.dtype = (config.oavi_kw or {}).get("dtype", "float32")
        self.scaler = MinMaxScaler(dtype=self.dtype)
        self.models: List = []
        self.svm = LinearSVM(config.svm)
        self.classes_: Optional[np.ndarray] = None
        self.stats: Dict = {}
        self.engine = None  # optional serving TransformEngine (attach_engine)
        self.fit_states: List = []  # per-class FitState (capture_fit_state)

    def _fit_generator_models(self, Xcs) -> List:
        """Per-class generator construction through :func:`repro.api.fit_classes`
        (class-batched when the config is eligible, sequential otherwise)."""
        from .. import api

        cfg = self.config
        self.fit_states = []
        if cfg.capture_fit_state:
            if cfg.chunk_rows is None:
                raise ValueError(
                    "capture_fit_state=True requires chunk_rows (the "
                    "streaming fit path persists the Gram accumulators)"
                )
            models = []
            for Xc in Xcs:
                model = api.fit(
                    Xc,
                    method=cfg.method,
                    psi=cfg.psi,
                    backend=cfg.backend,
                    mesh=cfg.mesh,
                    chunk_rows=cfg.chunk_rows,
                    capture_state=True,
                    **dict(cfg.oavi_kw or {}),
                )
                models.append(model)
                self.fit_states.append(model.fit_state)
            return models
        return api.fit_classes(
            Xcs,
            method=cfg.method,
            psi=cfg.psi,
            backend=cfg.backend,
            mesh=cfg.mesh,
            class_batch=cfg.class_batch,
            chunk_rows=cfg.chunk_rows,
            **dict(cfg.oavi_kw or {}),
        )

    def _feature_transform(self, X) -> np.ndarray:
        from .. import api

        engine = self.engine
        if engine is not None and not engine.matches(self.models):
            engine = None  # models were refitted since attach_engine
        return np.asarray(
            api.feature_transform(
                self.models,
                X,
                batch_size=self.config.batch_size,
                dtype=self.dtype,
                engine=engine,
            )
        )

    def attach_engine(
        self,
        engine=None,
        *,
        mesh=None,
        data_axes=("data",),
        engine_config=None,
        warmup: bool = True,
    ):
        """Route ``transform`` / ``predict`` through a serving
        :class:`~repro.serving.engine.TransformEngine` (shape-bucketed, zero
        recompiles at varying query sizes, optionally ``shard_map``-sharded
        over ``mesh``).

        Builds one over ``self.models`` when ``engine`` is omitted.  Model
        sets without a fused term-book plan (VCA) keep the per-model
        fallback: the engine stays ``None`` and ``None`` is returned.
        """
        from ..serving.engine import EngineConfig, TransformEngine, UnsupportedModelError

        if engine is None:
            try:
                engine = TransformEngine(
                    self.models,
                    mesh=mesh,
                    data_axes=data_axes,
                    config=engine_config or EngineConfig(),
                )
            except UnsupportedModelError:
                self.engine = None
                return None
        elif not engine.matches(self.models):
            raise ValueError("engine was built for a different model set")
        if warmup:
            engine.warmup()  # idempotent: already-traced buckets are skipped
        self.engine = engine
        return engine

    def head(self, feats) -> np.ndarray:
        """Classifier head over precomputed (FT) features: SVM argmax.

        The cheap per-request tail of ``predict`` — the serving batcher
        applies it after the coalesced feature transform."""
        return self.svm.predict(np.asarray(feats))

    def fit(self, X, y) -> "VanishingIdealClassifier":
        from .. import api

        # each span wraps the statements its ``stats`` time covers
        with obs.span("pipeline/fit"):
            t0 = time.perf_counter()
            # an engine attached to a previous fit's models would be silently
            # bypassed by matches() on every call while pinning the old model
            # set and its compiled buckets — drop it; re-attach_engine() after
            self.engine = None
            with obs.span("pipeline/scale"):
                X = self.scaler.fit_transform(X)
            y = np.asarray(y)
            self.classes_ = np.unique(y)
            with obs.span("pipeline/generators"):
                self.models = self._fit_generator_models(
                    [X[y == c] for c in self.classes_]
                )
            gen_stats = [m.stats for m in self.models]
            t_gen = time.perf_counter() - t0
            with obs.span("pipeline/transform"):
                t1 = time.perf_counter()
                Xt = self._feature_transform(X)
                t_transform = time.perf_counter() - t1
            with obs.span("pipeline/svm"):
                t2 = time.perf_counter()
                self.svm.fit(Xt, y)
                t_svm = time.perf_counter() - t2
            # recompiles/regrowths: class-batched groups share one compile
            # schedule — aggregate once per group, not once per class
            agg = api.aggregate_fit_stats(self.models)
            self.stats = {
                "time_generators": t_gen,
                "time_transform": t_transform,
                "time_svm": t_svm,
                "time_total": time.perf_counter() - t0,
                "num_features": Xt.shape[1],
                "G_plus_O": sum(s.get("G_plus_O", 0) for s in gen_stats),
                "recompiles": agg["recompiles"],
                "regrowths": agg["regrowths"],
                "class_batched": agg["class_batched"],
                "solver_schedule_len": agg["solver_schedule_len"],
                "solver_escalations": agg["solver_escalations"],
                "per_class": gen_stats,
                "svm": self.svm.stats,
            }
            if "class_batch_padding" in agg:
                self.stats["class_batch_padding"] = agg["class_batch_padding"]
        return self

    def transform(self, X) -> np.ndarray:
        return self._feature_transform(self.scaler.transform(X))

    def predict(self, X) -> np.ndarray:
        return self.svm.predict(self.transform(X))

    def score(self, X, y) -> float:
        return float(np.mean(self.predict(X) == np.asarray(y)))

    # -- reporting helpers (Table 3 quantities) ---------------------------

    def average_degree(self) -> float:
        degs = []
        for model in self.models:
            gens = getattr(model, "generators", None)
            if gens is not None:
                degs += [sum(g.term) for g in gens]
        return float(np.mean(degs)) if degs else 0.0

    def sparsity(self) -> float:
        """(SPAR): fraction of zero non-leading coefficients over all G."""
        z = e = 0
        for model in self.models:
            gens = getattr(model, "generators", None)
            if gens is None:
                continue
            for g in gens:
                e += len(g.coeffs)
                z += int(np.sum(g.coeffs == 0.0))
        return z / e if e else 0.0

    # -- serialization (serving: registry load / hot-swap) ------------------

    def to_state_dict(self) -> Tuple[Dict[str, np.ndarray], Dict]:
        """Flat array tree + JSON-safe metadata for the WHOLE pipeline:
        scaler, per-class generator models, and the SVM head — everything a
        serving process needs to answer predict requests."""
        from .. import api

        if self.svm.W is None or self.classes_ is None:
            raise ValueError("cannot serialize an unfitted classifier")
        arrays: Dict[str, np.ndarray] = {}
        model_metas = []
        for i, model in enumerate(self.models):
            a, meta = model.to_state_dict()
            if meta.get("kind") not in api._MODEL_KINDS:
                raise ValueError(
                    f"per-class model {i} has unserializable kind {meta.get('kind')!r}"
                )
            for k, v in a.items():
                arrays[f"model_{i:03d}.{k}"] = v
            model_metas.append(meta)
        arrays["scaler_lo"] = np.asarray(self.scaler.lo)
        arrays["scaler_scale"] = np.asarray(self.scaler.scale)
        arrays["svm_W"] = np.asarray(self.svm.W)
        arrays["svm_b"] = np.asarray(self.svm.b)
        arrays["classes"] = np.asarray(self.classes_)
        cfg = self.config
        meta = {
            "kind": "classifier",
            "num_models": len(self.models),
            "models": model_metas,
            "dtype": self.dtype,
            "config": {
                "method": cfg.method,
                "psi": cfg.psi,
                "svm": dataclasses.asdict(cfg.svm),
                "oavi_kw": cfg.oavi_kw,
                "backend": cfg.backend,
                "batch_size": cfg.batch_size,
                "class_batch": cfg.class_batch,
                "chunk_rows": cfg.chunk_rows,
                "capture_fit_state": cfg.capture_fit_state,
            },
            "svm_stats": self.svm.stats,
            "stats": self.stats,
        }
        return arrays, meta

    @classmethod
    def from_state_dict(
        cls, arrays: Dict[str, np.ndarray], meta: Dict
    ) -> "VanishingIdealClassifier":
        from .. import api

        cfg_meta = meta["config"]
        config = PipelineConfig(
            method=cfg_meta["method"],
            psi=cfg_meta["psi"],
            svm=LinearSVMConfig(**cfg_meta["svm"]),
            oavi_kw=cfg_meta["oavi_kw"],
            backend=cfg_meta["backend"],
            batch_size=cfg_meta["batch_size"],
            # pre-class-batch checkpoints lack the key; 'auto' is the default
            class_batch=cfg_meta.get("class_batch", "auto"),
            # pre-streaming checkpoints lack the key; None = in-memory fits
            chunk_rows=cfg_meta.get("chunk_rows"),
            capture_fit_state=cfg_meta.get("capture_fit_state", False),
        )
        clf = cls(config)
        clf.scaler.lo = np.asarray(arrays["scaler_lo"])
        clf.scaler.scale = np.asarray(arrays["scaler_scale"])
        clf.models = []
        for i, model_meta in enumerate(meta["models"]):
            prefix = f"model_{i:03d}."
            sub = {
                k[len(prefix):]: v for k, v in arrays.items() if k.startswith(prefix)
            }
            model_cls = api._MODEL_KINDS[model_meta["kind"]]
            clf.models.append(model_cls.from_state_dict(sub, model_meta))
        clf.svm.W = np.asarray(arrays["svm_W"])
        clf.svm.b = np.asarray(arrays["svm_b"])
        clf.svm.classes_ = np.asarray(arrays["classes"])
        clf.svm.stats = dict(meta.get("svm_stats") or {})
        clf.classes_ = np.asarray(arrays["classes"])
        clf.stats = dict(meta.get("stats") or {})
        return clf

    def save(self, path: str) -> str:
        """Persist the fitted pipeline to ``path`` (a directory) atomically
        via the checkpoint manifest machinery (same layout as
        :func:`repro.api.save`, format :data:`CLASSIFIER_FORMAT`)."""
        from .. import api

        arrays, meta = self.to_state_dict()
        return api.save_state_dict(path, arrays, meta, CLASSIFIER_FORMAT)

    @classmethod
    def load(cls, path: str) -> "VanishingIdealClassifier":
        """Load a pipeline written by :meth:`save` (bit-identical predict)."""
        from .. import api

        arrays, metadata = api.load_state_dict(path, CLASSIFIER_FORMAT)
        return cls.from_state_dict(arrays, metadata["meta"])
