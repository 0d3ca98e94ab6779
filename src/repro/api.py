"""Unified estimator API for vanishing-ideal generator construction.

One entry point over every algorithm family in the repo:

* **method registry** — algorithms register themselves with
  :func:`register`; callers pick one with a spec string such as ``"oavi"``,
  ``"oavi:bpcgavi-wihb"``, ``"abm"`` or ``"vca"`` (bare OAVI variant names
  like ``"cgavi-ihb"`` are accepted for backward compatibility).
  :func:`available_methods` lists every valid spec.
* **backend dispatch** — :func:`fit` routes OAVI to
  :mod:`repro.core.distributed` when a mesh is supplied (or, under
  ``backend="auto"``, when multiple devices are visible and ``m`` is large
  enough), so callers never import the distributed module directly.
* **VanishingIdealModel protocol** — every fitted model exposes
  ``evaluate_G`` / ``transform`` / ``to_state_dict`` / ``from_state_dict``;
  :func:`save` / :func:`load` persist models through the atomic
  :mod:`repro.checkpoint.store` manifest machinery, so a fitted model
  survives restarts and can be shipped to a serving process.
* **fused batched transform** — :func:`feature_transform` concatenates all
  per-class term books and generator matrices into a *single* jitted
  ``evaluate_terms`` call plus one matmul, with ``batch_size`` chunking so
  million-row transforms stream through device memory.
* **class-batched multi-class fitting** — :func:`fit_classes` (or
  :func:`fit` with a list of per-class arrays) drives eligible per-class
  OAVI fits through one vmapped degree step (:mod:`repro.core.class_batch`)
  grouped into shared pow2 row buckets, falling back to sequential fits for
  stragglers and non-batchable configs; :func:`aggregate_fit_stats` folds
  the per-group compile counters into classifier-level totals.
"""

from __future__ import annotations

import dataclasses
import json
import logging
from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Protocol,
    Sequence,
    Tuple,
    Union,
    runtime_checkable,
)

import numpy as np

# Canonical OAVI variant table (was ``pipeline.VARIANTS``; Section 6.1).
# name: (engine, solver, ihb, wihb)
OAVI_VARIANTS: Dict[str, Tuple[str, str, bool, bool]] = {
    "cgavi-ihb": ("oracle", "cg", True, False),
    "agdavi-ihb": ("oracle", "agd", True, False),
    "bpcgavi": ("oracle", "bpcg", False, False),
    "bpcgavi-wihb": ("oracle", "bpcg", True, True),
    "pcgavi": ("oracle", "pcg", False, False),
    "cgavi": ("oracle", "cg", False, False),
    "agdavi": ("oracle", "agd", False, False),
    "fast": ("fast", "bpcg", True, False),  # beyond-paper closed-form engine
}

# OAVI_VARIANTS must be defined before the core imports below:
# ``repro.core.pipeline`` lazily imports this module for its deprecated
# ``VARIANTS`` alias, which may happen while this module is mid-import.
import jax
import jax.numpy as jnp

from . import obs
from . import streaming as streaming_mod
from .checkpoint import store as ckpt_store
from .core import abm as abm_mod
from .core import class_batch as class_batch_mod
from .core import distributed as distributed_mod
from .core import oavi as oavi_mod
from .core import vca as vca_mod
from .core.oavi import OAVIModel, apply_wavefronts, wavefront_schedule
from .core.oracles import OracleConfig
from .core.transform import feature_transform as _legacy_feature_transform
from .core.vca import VCAModel
from .resilience.integrity import IntegrityError

_log = logging.getLogger("repro.api")

# ``backend="auto"``: shard only when the sample count amortizes the psum +
# shard_map overhead (the collectives are m-independent, the fixed cost isn't).
AUTO_SHARD_MIN_M = 100_000


# ---------------------------------------------------------------------------
# VanishingIdealModel protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class VanishingIdealModel(Protocol):
    """What every fitted generator model exposes (OAVIModel, VCAModel, ...)."""

    n: int
    psi: float
    stats: Dict

    def evaluate_G(self, Z) -> Any:
        """Evaluation matrix of all generators over Z: (q, |G|)."""
        ...

    def transform(self, Z) -> np.ndarray:
        """(FT) features for this model alone: ``|G(Z)|``."""
        ...

    def to_state_dict(self) -> Tuple[Dict[str, np.ndarray], Dict]:
        """(flat array tree, JSON-safe metadata) — see :func:`save`."""
        ...

    def save(self, path: str) -> str:
        """Persist via :func:`repro.api.save`."""
        ...


# ---------------------------------------------------------------------------
# Method registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MethodEntry:
    """A registered generator-construction algorithm."""

    name: str
    fit: Callable[..., VanishingIdealModel]
    variants: Tuple[str, ...] = ()
    default_variant: Optional[str] = None
    supports_sharded: bool = False
    description: str = ""

    def spec(self, variant: Optional[str]) -> str:
        return f"{self.name}:{variant}" if variant else self.name


_REGISTRY: Dict[str, MethodEntry] = {}


def register(
    name: str,
    *,
    variants: Sequence[str] = (),
    default_variant: Optional[str] = None,
    supports_sharded: bool = False,
    description: str = "",
):
    """Decorator: register ``fn(X, *, variant, psi, backend, mesh, data_axes,
    config, **kw) -> VanishingIdealModel`` under ``name``."""

    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"method {name!r} is already registered")
        _REGISTRY[name] = MethodEntry(
            name=name,
            fit=fn,
            variants=tuple(variants),
            default_variant=default_variant,
            supports_sharded=supports_sharded,
            description=description,
        )
        return fn

    return deco


def available_methods() -> Tuple[str, ...]:
    """Every valid ``method=`` spec, e.g. ``('abm', 'oavi', 'oavi:cgavi', ...)``."""
    specs: List[str] = []
    for name in sorted(_REGISTRY):
        specs.append(name)
        specs.extend(f"{name}:{v}" for v in _REGISTRY[name].variants)
    return tuple(specs)


def resolve(spec: str) -> Tuple[MethodEntry, Optional[str]]:
    """``'oavi:cgavi-ihb'`` -> (oavi entry, 'cgavi-ihb').  Also accepts bare
    method names (default variant) and bare OAVI variant names (legacy)."""
    if not isinstance(spec, str):
        raise TypeError(f"method spec must be a string, got {type(spec).__name__}")
    if ":" in spec:
        name, variant = spec.split(":", 1)
        entry = _REGISTRY.get(name)
        if entry is None:
            raise ValueError(
                f"unknown method {name!r}; available: {', '.join(available_methods())}"
            )
        if variant not in entry.variants:
            raise ValueError(
                f"unknown variant {variant!r} for method {name!r}; "
                f"available: {', '.join(entry.variants) or '(none)'}"
            )
        return entry, variant
    if spec in _REGISTRY:
        entry = _REGISTRY[spec]
        return entry, entry.default_variant
    # legacy: bare OAVI variant names ("cgavi-ihb", "fast", ...)
    for entry in _REGISTRY.values():
        if spec in entry.variants:
            return entry, spec
    raise ValueError(
        f"unknown method {spec!r}; available: {', '.join(available_methods())}"
    )


# ---------------------------------------------------------------------------
# Registered methods
# ---------------------------------------------------------------------------


def oavi_config_for(variant: str, psi: float, **kw) -> oavi_mod.OAVIConfig:
    """Build an :class:`OAVIConfig` from a named paper variant."""
    engine, solver, ihb, wihb = OAVI_VARIANTS[variant]
    solver_cfg = OracleConfig(name=solver, **kw.pop("solver_kw", {}))
    return oavi_mod.OAVIConfig(
        psi=psi, engine=engine, solver=solver_cfg, ihb=ihb, wihb=wihb, **kw
    )


@register(
    "oavi",
    variants=tuple(OAVI_VARIANTS),
    default_variant="fast",
    supports_sharded=True,
    description="Oracle AVI (Algorithm 1); variants per Section 6.1",
)
def _fit_oavi(X, *, variant, psi, backend, mesh, data_axes, config=None, **kw):
    cfg = config if config is not None else oavi_config_for(variant or "fast", psi, **kw)
    if backend == "sharded":
        return distributed_mod.fit(X, cfg, mesh=mesh, data_axes=data_axes)
    return oavi_mod.fit(X, cfg)


@register("abm", description="Approximate Buchberger-Möller (Limbeck 2013)")
def _fit_abm(X, *, variant, psi, backend, mesh, data_axes, config=None, **kw):
    cfg = config if config is not None else abm_mod.ABMConfig(psi=psi, **kw)
    return abm_mod.fit(X, cfg)


@register("vca", description="Vanishing Component Analysis (Livni et al. 2013)")
def _fit_vca(X, *, variant, psi, backend, mesh, data_axes, config=None, **kw):
    cfg = config if config is not None else vca_mod.VCAConfig(psi=psi, **kw)
    return vca_mod.fit(X, cfg)


# ---------------------------------------------------------------------------
# Backend dispatch
# ---------------------------------------------------------------------------


def _default_mesh(data_axes: Sequence[str]):
    axes = tuple(data_axes)
    if len(axes) != 1:
        raise ValueError(
            "backend dispatch can only build a default mesh for a single data "
            f"axis; pass mesh= explicitly for data_axes={axes!r}"
        )
    return jax.make_mesh((len(jax.devices()),), axes)


def _resolve_backend(
    entry: MethodEntry, backend: str, mesh, m: int
) -> Tuple[str, Any]:
    if backend not in ("auto", "local", "sharded"):
        raise ValueError(
            f"unknown backend {backend!r}; expected 'auto', 'local' or 'sharded'"
        )
    if backend == "local":
        return "local", None
    if backend == "sharded":
        if not entry.supports_sharded:
            raise ValueError(
                f"method {entry.name!r} does not support backend='sharded'"
            )
        return "sharded", mesh
    # auto: shard when the method can, and a mesh was supplied or the device
    # count and sample count justify it.
    if entry.supports_sharded and (
        mesh is not None or (len(jax.devices()) > 1 and m >= AUTO_SHARD_MIN_M)
    ):
        return "sharded", mesh
    return "local", None


def fit(
    X,
    method: str = "oavi",
    *,
    psi: float = 0.005,
    backend: str = "auto",
    mesh=None,
    data_axes: Sequence[str] = ("data",),
    out_sharding=None,
    config=None,
    class_batch: str = "auto",
    source=None,
    chunk_rows: Optional[int] = None,
    capture_state: bool = False,
    **method_kw,
) -> Union[VanishingIdealModel, List[VanishingIdealModel]]:
    """Fit a vanishing-ideal model with the selected ``method`` and backend.

    Parameters
    ----------
    X : (m, n) array in ``[0, 1]^n`` — or a *list* of per-class arrays, in
        which case one model is fitted per class (see :func:`fit_classes`)
        and a list of models is returned — or a
        :class:`repro.streaming.DataSource`, which routes to the out-of-core
        streaming fit (equivalent to passing it as ``source=``).
    method : spec string — ``"oavi"``, ``"oavi:<variant>"``, ``"abm"``,
        ``"vca"``; see :func:`available_methods`.
    psi : vanishing tolerance.
    backend : ``"auto"`` (default) picks ``"sharded"`` for OAVI when a mesh
        is supplied or >1 device is visible and ``m >= AUTO_SHARD_MIN_M``;
        otherwise ``"local"``.
    mesh : optional :class:`jax.sharding.Mesh` for the sharded backend (a
        1-axis mesh over all devices is built when omitted).
    data_axes : mesh axes the sample dimension is sharded over.
    out_sharding : optional sharding hint attached to the returned model; the
        fused :func:`feature_transform` places its output there by default.
    config : pre-built method config (``OAVIConfig`` / ``ABMConfig`` /
        ``VCAConfig``); overrides ``psi`` and ``method_kw`` when given.
    class_batch : ``"auto"`` | ``"off"`` — multi-class fits only (``X`` a
        list): ``"auto"`` batches eligible per-class OAVI fits through one
        vmapped degree step (:mod:`repro.core.class_batch`).
    source : optional chunked data source (:mod:`repro.streaming`) — fits
        out-of-core through :func:`repro.streaming.fit`: the evaluation
        matrix is rematerialized per degree in ``chunk_rows``-row chunks and
        reduced to Gram statistics, so ``m`` is not bounded by device
        memory.  OAVI only; bit-exact vs the in-memory fit at matched
        capacity.  The source must already be scaled to ``[0, 1]^n``
        (compose with :class:`repro.streaming.ScaledSource`).
    chunk_rows : streaming chunk size (power of two, multiple of
        :data:`repro.kernels.ops.GRAM_BLOCK`); default
        :data:`repro.streaming.DEFAULT_CHUNK_ROWS`.  Setting it with an
        in-memory ``X`` (array or per-class list) streams through the
        array(s) as sources — same out-of-core fit path, OAVI only.
    capture_state : streaming OAVI fits only — also capture the incremental
        :class:`repro.online.FitState` (attached as ``model.fit_state``) so
        the model can later be refreshed in place with :func:`update` when
        the source grows.  Local backend only.
    **method_kw : forwarded to the method's config constructor (e.g.
        ``cap_terms=64``, ``solver_kw={"max_iter": 2000}``).
    """
    if source is None and streaming_mod.is_source(X):
        source, X = X, None
    if source is None and chunk_rows is not None and not isinstance(X, (list, tuple)):
        # chunk_rows on an in-memory array: stream through it as a source
        # (the fit never materializes the (m, Lcap) evaluation matrix)
        source, X = streaming_mod.as_source(np.asarray(X)), None
    if source is not None:
        return _fit_streaming(
            source,
            method,
            psi=psi,
            backend=backend,
            mesh=mesh,
            data_axes=data_axes,
            config=config,
            chunk_rows=chunk_rows,
            out_sharding=out_sharding,
            capture_state=capture_state,
            **method_kw,
        )
    if capture_state:
        raise ValueError(
            "capture_state=True needs the streaming fit path: pass source= "
            "(or an in-memory X together with chunk_rows=)"
        )
    if isinstance(X, (list, tuple)):
        return fit_classes(
            X,
            method,
            psi=psi,
            backend=backend,
            mesh=mesh,
            data_axes=data_axes,
            class_batch=class_batch,
            config=config,
            chunk_rows=chunk_rows,
            **method_kw,
        )
    if class_batch not in ("auto", "off"):
        raise ValueError(
            f"unknown class_batch {class_batch!r}; expected 'auto' or 'off'"
        )
    entry, variant = resolve(method)
    X = np.asarray(X)
    backend_r, mesh_r = _resolve_backend(entry, backend, mesh, X.shape[0])
    if backend_r == "sharded" and mesh_r is None:
        mesh_r = _default_mesh(data_axes)
    model = entry.fit(
        X,
        variant=variant,
        psi=psi,
        backend=backend_r,
        mesh=mesh_r,
        data_axes=tuple(data_axes),
        config=config,
        **method_kw,
    )
    model.stats["api"] = {"method": entry.spec(variant), "backend": backend_r}
    if out_sharding is not None:
        model.transform_out_sharding = out_sharding
    return model


def _fit_streaming(
    source,
    method: str,
    *,
    psi: float,
    backend: str,
    mesh,
    data_axes: Sequence[str],
    config,
    chunk_rows: Optional[int],
    out_sharding=None,
    capture_state: bool = False,
    **method_kw,
):
    """Out-of-core dispatch: route an OAVI spec to :func:`repro.streaming.fit`
    (or, with ``capture_state``, to :func:`repro.online.fit` — same fold,
    same caches, plus the persisted accumulators)."""
    entry, variant = resolve(method)
    if entry.name != "oavi":
        raise ValueError(
            f"streaming fit (source=) supports OAVI only, got method {method!r}"
        )
    cfg = config if config is not None else oavi_config_for(variant or "fast", psi, **method_kw)
    source = streaming_mod.as_source(source)
    backend_r, mesh_r = _resolve_backend(entry, backend, mesh, source.num_rows)
    if capture_state:
        if backend_r == "sharded":
            raise ValueError(
                "capture_state=True is local-only (an incremental update is "
                "O(new rows); run full sharded refits without it)"
            )
        from . import online as online_mod

        model, fit_state = online_mod.fit(
            source, cfg, chunk_rows=chunk_rows or streaming_mod.DEFAULT_CHUNK_ROWS
        )
        model.stats["api"] = {
            "method": entry.spec(variant),
            "backend": backend_r,
            "streaming": True,
            "online": True,
        }
        model.fit_state = fit_state
        if out_sharding is not None:
            model.transform_out_sharding = out_sharding
        return model
    if backend_r == "sharded" and mesh_r is None:
        mesh_r = _default_mesh(data_axes)
    model = streaming_mod.fit(
        source,
        cfg,
        chunk_rows=chunk_rows or streaming_mod.DEFAULT_CHUNK_ROWS,
        mesh=mesh_r if backend_r == "sharded" else None,
        data_axes=tuple(data_axes),
    )
    model.stats["api"] = {
        "method": entry.spec(variant),
        "backend": backend_r,
        "streaming": True,
    }
    if out_sharding is not None:
        model.transform_out_sharding = out_sharding
    return model


def update(model, state, source, **kw):
    """Refresh a :func:`fit(..., capture_state=True) <fit>` model in place
    after its source grew.

    Folds only the new rows into ``state``'s persisted per-degree Gram
    accumulators and re-runs the m-independent degree steps — bit-identical
    to refitting from scratch on the grown source at matched capacity, at
    O(new rows) cost and zero recompiles warm.  Returns the
    :class:`repro.online.UpdateResult` whose ``.model`` carries a fresh
    ``fit_state`` for the next increment.  See :func:`repro.online.update`
    for keyword arguments (``chunk_rows``, ``scaler``, ``prefetch``, ...).
    """
    from . import online as online_mod

    result = online_mod.update(model, state, source, **kw)
    api_stats = dict(getattr(model, "stats", {}).get("api") or {})
    api_stats.update({"backend": "local", "streaming": True, "online": True})
    result.model.stats["api"] = api_stats
    result.model.fit_state = result.state
    return result


# ---------------------------------------------------------------------------
# Multi-class fitting: class-batched when eligible, sequential otherwise
# ---------------------------------------------------------------------------


def fit_classes(
    Xs: Sequence,
    method: str = "oavi",
    *,
    psi: float = 0.005,
    backend: str = "auto",
    mesh=None,
    data_axes: Sequence[str] = ("data",),
    class_batch: str = "auto",
    config=None,
    chunk_rows: Optional[int] = None,
    **method_kw,
) -> List[VanishingIdealModel]:
    """Fit one model per class — Algorithm 2's generator-construction phase.

    With ``class_batch="auto"`` (default) and an eligible OAVI config
    (:func:`repro.core.oavi.class_batchable`: every engine with the Theorem
    4.9 ``inverse`` — the ``fast`` closed form AND the oracle solvers/WIHB,
    which run under ``vmap`` too; only the
    Cholesky engine is excluded), classes are grouped into shared pow2 row
    buckets (:func:`repro.core.class_batch.plan_class_groups`: greedy
    buckets, cross-bucket merges while padding stays ~2x, and straggler
    classes folded into their cheapest warm bucket rather than fitted
    sequentially) and every group is fitted through ONE vmapped jitted degree
    step (:func:`repro.core.class_batch.fit_classes`) — bit-exact against
    the sequential path at matched capacity, one dispatch per degree instead
    of k.  Non-OAVI methods and non-batchable configs fall back to per-class
    :func:`fit`.  Each batched model's ``stats["class_batch_padding"]``
    reports the padded-row bill its group paid.

    The sharded backend composes: when ``backend`` resolves to
    ``"sharded"``, batched groups run the vmap-inside-``shard_map`` step
    over ``mesh`` (class axis replicated, sample axis sharded).

    With ``chunk_rows`` (out-of-core classes) and a local backend, batchable
    configs route through :func:`repro.streaming.fit_classes`: each class
    streams its own chunks, and the per-degree acceptance decisions run as
    one vmapped statistics-only step — no row padding at all (streaming has
    no shared row bucket).  Sharded streaming stays per-class.

    Returns the fitted models in class order.  Batched models' stats carry a
    ``"class_batch"`` group dict whose shared ``recompiles`` / ``regrowths``
    must be aggregated once per group — use :func:`aggregate_fit_stats`.
    """
    if class_batch not in ("auto", "off"):
        raise ValueError(
            f"unknown class_batch {class_batch!r}; expected 'auto' or 'off'"
        )
    entry, variant = resolve(method)
    Xs = [np.asarray(X) for X in Xs]

    def seq_fit(X):
        if chunk_rows is not None and entry.name == "oavi":
            # out-of-core per-class fits: each class streams through the
            # chunk accumulator (bit-exact vs its in-memory fit); used when
            # the vmapped streaming class batch doesn't apply (sharded
            # streaming, non-batchable configs)
            return fit(
                X,
                method,
                psi=psi,
                backend=backend,
                mesh=mesh,
                data_axes=data_axes,
                config=config,
                source=streaming_mod.as_source(X),
                chunk_rows=chunk_rows,
                **dict(method_kw),
            )
        return fit(
            X,
            method,
            psi=psi,
            backend=backend,
            mesh=mesh,
            data_axes=data_axes,
            config=config,
            **dict(method_kw),
        )

    if class_batch == "off" or entry.name != "oavi" or len(Xs) < 2:
        return [seq_fit(X) for X in Xs]
    cfg = (
        config
        if config is not None
        else oavi_config_for(variant or "fast", psi, **dict(method_kw))
    )
    if not oavi_mod.class_batchable(cfg):
        return [seq_fit(X) for X in Xs]  # chol engine only: sequential

    backend_r, mesh_r = _resolve_backend(
        entry, backend, mesh, max(X.shape[0] for X in Xs)
    )
    if backend_r == "sharded" and mesh_r is None:
        mesh_r = _default_mesh(data_axes)

    if chunk_rows is not None:
        if backend_r == "sharded":
            # sharded streaming stays per-class (the vmapped streaming stats
            # step is local-only)
            return [seq_fit(X) for X in Xs]
        fitted = streaming_mod.fit_classes(Xs, cfg, chunk_rows=chunk_rows)
        for model in fitted:
            model.stats["api"] = {
                "method": entry.spec(variant),
                "backend": backend_r,
                "streaming": True,
                "class_batch": True,
            }
        return list(fitted)

    models: List[Optional[VanishingIdealModel]] = [None] * len(Xs)
    sizes = [X.shape[0] for X in Xs]
    for cap, idxs in class_batch_mod.plan_class_groups(sizes):
        fitted = class_batch_mod.fit_classes(
            [Xs[i] for i in idxs],
            cfg,
            mesh=mesh_r if backend_r == "sharded" else None,
            data_axes=tuple(data_axes),
            m_cap=cap,
        )
        # the dispatched row bucket (>= cap: sharding may round up)
        mc = int(fitted[0].stats["class_batch"]["m_cap"])
        group_rows = sum(sizes[i] for i in idxs)
        group_padded = mc * len(idxs) - group_rows
        for i, model in zip(idxs, fitted):
            model.stats["api"] = {
                "method": entry.spec(variant),
                "backend": backend_r,
                "class_batch": True,
            }
            model.stats["class_batch_padding"] = {
                "m_cap": mc,
                "rows": int(sizes[i]),
                "padded_rows": mc - int(sizes[i]),
                "group_rows": int(group_rows),
                "group_padded_rows": int(group_padded),
                # fraction of the group's dispatched rows that are padding
                "waste": group_padded / float(mc * len(idxs)),
            }
            models[i] = model
    return models


def aggregate_fit_stats(models: Sequence) -> Dict:
    """Classifier-level fit counters over per-class models.

    Class-batched models share ONE compile/regrowth schedule per batch group
    (their per-model stats all carry the same counts), so naively summing
    per-class stats overcounts by the group size; this counts each group
    once and each sequentially-fitted model individually.
    ``class_batch_padding`` rolls the per-model padding accounting up to
    dispatched/padded row totals and the overall waste fraction, mirrored
    into the metric registry (``fit.class_batch_padding_waste``) so
    obs_report sees the classifier-level view."""
    recompiles = regrowths = 0
    batched = 0
    groups = set()
    pad_groups = set()
    dispatched_rows = padded_rows = 0
    for model in models:
        stats = getattr(model, "stats", None) or {}
        group = stats.get("class_batch")
        padding = stats.get("class_batch_padding")
        if padding is not None:
            # group totals are replicated on every member; count each once
            pad_key = (padding["m_cap"], padding["group_rows"],
                       padding["group_padded_rows"])
            if pad_key not in pad_groups:
                pad_groups.add(pad_key)
                dispatched_rows += int(padding["group_rows"]) + int(
                    padding["group_padded_rows"]
                )
                padded_rows += int(padding["group_padded_rows"])
        if group is not None:
            batched += 1
            if group["group"] in groups:
                continue
            groups.add(group["group"])
            recompiles += int(group["recompiles"])
            regrowths += int(group["regrowths"])
        else:
            recompiles += int(stats.get("recompiles", 0))
            regrowths += int(stats.get("regrowths", 0))
    out: Dict = {
        "recompiles": recompiles,
        "regrowths": regrowths,
        "class_batched": batched,
        "class_batch_groups": len(groups),
    }
    if dispatched_rows:
        out["class_batch_padding"] = {
            "dispatched_rows": dispatched_rows,
            "padded_rows": padded_rows,
            "waste": padded_rows / float(dispatched_rows),
        }
    if obs.enabled() and dispatched_rows:
        obs.registry().gauge("fit.class_batch_padding_waste").set(
            padded_rows / float(dispatched_rows)
        )
    return out


# ---------------------------------------------------------------------------
# Serialization: save / load through the checkpoint manifest machinery
# ---------------------------------------------------------------------------

_MODEL_KINDS: Dict[str, Any] = {"oavi": OAVIModel, "vca": VCAModel}
_FORMAT = "repro.vanishing_ideal_model.v1"


def _json_safe(obj):
    """Recursively convert numpy scalars/arrays so metadata JSON-serializes."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, np.bool_):
        return bool(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    return obj


def save_state_dict(path: str, arrays: Dict, meta: Dict, fmt: str, step: int = 0) -> str:
    """Write one ``(arrays, meta)`` state dict as a committed, format-tagged
    checkpoint — the single save-side protocol shared by :func:`save` and
    :meth:`VanishingIdealClassifier.save`.  Arrays land as manifest-tracked
    leaves, ``meta`` (made JSON-safe) in the manifest, and the COMMITTED
    marker makes the write crash-safe.  Returns the committed directory.

    ``step`` versions the save inside ``path``: a caller that checkpoints a
    lineage (e.g. the continuous controller's per-version ``FitState``)
    bumps it so :func:`load_state_dict` has older committed steps to fall
    back to when the head is corrupted after commit."""
    metadata = {
        "format": fmt,
        "kind": meta.get("kind"),
        "meta": _json_safe(meta),
        "array_keys": sorted(arrays),
    }
    return ckpt_store.save(path, step=step, tree=dict(arrays), metadata=metadata)


def load_state_dict(path: str, fmt: str) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Load the newest *verifiable* committed state dict at ``path``,
    checking its format tag — the restore-side counterpart of
    :func:`save_state_dict`.

    Every leaf is checksum-verified before deserializing (manifest v2); a
    corrupt head step falls back to the newest older committed step that
    verifies, so post-commit bit rot costs freshness, not availability.
    When every committed step is damaged, the head's
    :class:`~repro.resilience.integrity.IntegrityError` (naming the bad
    file) propagates."""
    steps = ckpt_store.committed_steps(path)
    if not steps:
        raise FileNotFoundError(f"no committed checkpoint under {path!r}")
    head_err: Optional[IntegrityError] = None
    for step in reversed(steps):
        try:
            metadata, _ = ckpt_store.read_metadata(path, step)
            if metadata.get("format") != fmt:
                raise ValueError(
                    f"{path!r} is not a {fmt} checkpoint "
                    f"(format={metadata.get('format')!r})"
                )
            like = {k: np.zeros(()) for k in metadata["array_keys"]}
            arrays, metadata = ckpt_store.restore(path, step, like)
        except (IntegrityError, json.JSONDecodeError) as e:
            _log.warning("checkpoint step %d at %r failed verification: %s", step, path, e)
            if head_err is None:
                head_err = e if isinstance(e, IntegrityError) else IntegrityError(str(e))
            continue
        if step != steps[-1]:
            _log.warning(
                "loaded step %d from %r (newest committed step %d is corrupt)",
                step, path, steps[-1],
            )
        return arrays, metadata
    raise head_err


def save(model: VanishingIdealModel, path: str) -> str:
    """Persist a fitted model to ``path`` (a directory) atomically."""
    arrays, meta = model.to_state_dict()
    kind = meta.get("kind")
    if kind not in _MODEL_KINDS:
        raise ValueError(f"cannot save model of unknown kind {kind!r}")
    return save_state_dict(path, arrays, meta, _FORMAT)


def load(path: str) -> VanishingIdealModel:
    """Load a model previously written by :func:`save` (bit-identical)."""
    arrays, metadata = load_state_dict(path, _FORMAT)
    cls = _MODEL_KINDS[metadata["kind"]]
    return cls.from_state_dict(arrays, metadata["meta"])


# ---------------------------------------------------------------------------
# Fused batched transform
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _FusedPlan:
    """All per-class term books and generator matrices concatenated into one
    global book (constant term shared at index 0) so the whole (FT) is one
    ``evaluate_terms`` call plus one matmul."""

    parents: np.ndarray  # (L,) int32 — global term book parent chain
    vars: np.ndarray  # (L,) int32 — variable indices in ORIGINAL Z coords
    C: np.ndarray  # (L, Ktot) — block-diagonal generator coefficients
    gp: np.ndarray  # (Ktot,) int32 — leading-term parent (global index)
    gv: np.ndarray  # (Ktot,) int32 — leading-term variable (original coords)
    dtype: np.dtype
    num_features: int
    n: int  # input dimension (original Z coordinates)


def _fuse(models: Sequence) -> Optional[_FusedPlan]:
    """Build the fused plan, or None when a model is not term-book based
    (e.g. VCA) — callers fall back to the per-model loop."""
    models = [m for m in models]
    if not models or not all(type(m) is OAVIModel for m in models):
        return None
    n = models[0].n
    if any(m.n != n for m in models):
        return None
    dtype = np.dtype(models[0].dtype)
    if any(np.dtype(m.dtype) != dtype for m in models):
        return None  # mixed precision: evaluate each model in its own dtype
    g_parents: List[np.ndarray] = [np.zeros((1,), np.int32)]
    g_vars: List[np.ndarray] = [np.zeros((1,), np.int32)]
    c_blocks: List[Tuple[int, np.ndarray]] = []  # (row offset, (ell_b, k_b))
    gp_all: List[np.ndarray] = []
    gv_all: List[np.ndarray] = []
    offset = 1  # global slot of each model's first non-constant term
    for m in models:
        if m.num_G == 0:
            continue  # contributes no feature columns; skip its book entirely
        perm = (
            np.asarray(m.feature_perm, np.int64)
            if m.feature_perm is not None
            else np.arange(n, dtype=np.int64)
        )
        pb, vb = m.term_arrays()
        ell = pb.shape[0]
        C, gp, gv = m.generator_arrays()
        c_blocks.append((offset, C.astype(dtype, copy=False)))
        gp_all.append(np.where(gp == 0, 0, offset + gp - 1).astype(np.int32))
        gv_all.append(perm[gv].astype(np.int32))
        if ell > 1:
            g_parents.append(
                np.where(pb[1:] == 0, 0, offset + pb[1:] - 1).astype(np.int32)
            )
            g_vars.append(perm[vb[1:]].astype(np.int32))
        offset += ell - 1
    L = offset
    parents = np.concatenate(g_parents)
    vars_ = np.concatenate(g_vars)
    num_features = sum(b.shape[1] for _, b in c_blocks)
    C = np.zeros((L, num_features), dtype)
    col = 0
    for row_off, Cb in c_blocks:
        k = Cb.shape[1]
        C[0, col : col + k] = Cb[0]  # constant-term coefficients
        C[row_off : row_off + Cb.shape[0] - 1, col : col + k] = Cb[1:]
        col += k
    gp = np.concatenate(gp_all) if gp_all else np.zeros((0,), np.int32)
    gv = np.concatenate(gv_all) if gv_all else np.zeros((0,), np.int32)
    return _FusedPlan(
        parents=parents,
        vars=vars_,
        C=C,
        gp=gp,
        gv=gv,
        dtype=dtype,
        num_features=num_features,
        n=n,
    )


@dataclasses.dataclass(frozen=True)
class PlanConstants:
    """Trace-constant arrays of the fused (FT) evaluation, hoisted out of the
    jitted function.

    Everything here depends only on the fitted models (via the
    :class:`_FusedPlan`), never on the query batch, so per-shape retraces
    reuse the same host arrays instead of rebuilding them — and the serving
    engine (:mod:`repro.serving.engine`) shares them across its shape
    buckets and its local / ``shard_map`` execution paths.
    """

    waves: Tuple  # wavefront schedule over the fused book
    C_w: np.ndarray  # (L, k) generator coefficients, wavefront row order
    GPsel: np.ndarray  # (L, k) one-hot: leading-term parent column selector
    GVsel: np.ndarray  # (n, k) one-hot: leading-term variable selector
    dtype: np.dtype
    num_features: int
    n: int


def plan_constants(plan: "_FusedPlan") -> PlanConstants:
    """Hoist every trace constant of the fused evaluation out of the traced
    function.

    The fused multi-book column order is not degree-grouped, so instead of
    permuting the wavefront output at runtime the permutation is folded into
    the constants: the generator matrix rows are pre-gathered into wavefront
    order and both leading-term selections (parent column and variable) are
    one-hot matmuls — the whole transform is matmuls, no runtime gathers.
    """
    waves, perm = wavefront_schedule(plan.parents, plan.vars)
    L = int(np.asarray(plan.parents).shape[0])
    k = plan.C.shape[1]
    if perm is not None:
        # cols_original = cols_wave[:, perm]  =>  cols_original @ C ==
        # cols_wave @ C[order] with order = argsort(perm)
        order = np.argsort(perm)
        C_w = np.ascontiguousarray(plan.C[order])
        gp_w = perm[plan.gp]  # original index -> wavefront column
    else:
        C_w = plan.C
        gp_w = plan.gp
    GPsel = np.zeros((L, k), np.float32)
    GPsel[gp_w, np.arange(k)] = 1.0
    GVsel = np.zeros((plan.n, k), np.float32)
    GVsel[np.asarray(plan.gv), np.arange(k)] = 1.0
    return PlanConstants(
        waves=waves,
        C_w=C_w,
        GPsel=GPsel,
        GVsel=GVsel,
        dtype=plan.dtype,
        num_features=plan.num_features,
        n=plan.n,
    )


def eval_with_constants(consts: PlanConstants, Z) -> jax.Array:
    """Fused (FT) body over hoisted constants: a degree-wavefront term sweep
    (all terms of a degree in one batched select-matmul step — O(max_degree)
    sequential steps instead of O(|O|)) plus one matmul.  Pure and
    traceable: callers wrap it in ``jax.jit`` and/or ``shard_map``."""
    cols = apply_wavefronts(Z, consts.waves)  # (q, L) in wavefront order
    hi = jax.lax.Precision.HIGHEST  # f32 selects and sums, also on TPU
    lead = jnp.matmul(cols, jnp.asarray(consts.GPsel, Z.dtype), precision=hi) * (
        jnp.matmul(Z, jnp.asarray(consts.GVsel, Z.dtype), precision=hi)
    )
    return jnp.abs(jnp.matmul(cols, jnp.asarray(consts.C_w, Z.dtype), precision=hi) + lead)


def _make_fused_eval(plan: "_FusedPlan"):
    """Jitted fused (FT) evaluation for one plan (see
    :func:`eval_with_constants`; constants hoisted via
    :func:`plan_constants`)."""
    consts = plan_constants(plan)

    @jax.jit
    def fused_eval(Z):
        return eval_with_constants(consts, Z)

    return fused_eval


def _fused_plan_and_eval(models: Sequence):
    """Fused plan and its jitted wavefront evaluator, cached on the first
    model.

    The plan depends only on the fitted models, so serving loops calling
    :func:`feature_transform` repeatedly skip the per-call plan assembly and
    trace-constant upload.  The cache entry holds strong references to the
    models, which keeps their ids unique for as long as the key is live.
    """
    key = tuple(id(m) for m in models)
    cached = models[0].__dict__.get("_fused_plan_cache")
    if cached is not None and cached[0] == key:
        return cached[2], cached[3]
    plan = _fuse(models)
    if plan is None:
        return None, None
    fn = _make_fused_eval(plan)
    models[0].__dict__["_fused_plan_cache"] = (key, tuple(models), plan, fn)
    return plan, fn


def feature_transform(
    models: Sequence,
    Z,
    *,
    batch_size: Optional[int] = None,
    out_sharding=None,
    dtype: Optional[str] = None,
    engine=None,
) -> np.ndarray:
    """(FT) over all per-class models as ONE jitted evaluation.

    Drop-in replacement for :func:`repro.core.transform.feature_transform`:
    same output (within dtype tolerance), but all term books are evaluated in
    a single ``evaluate_terms`` sweep and all generators in one matmul.
    ``batch_size`` streams Z through device memory in fixed-size chunks (the
    trailing chunk is padded, so at most two jit traces exist).  Models
    without a term book (VCA) fall back to the per-model loop.

    ``engine`` routes the call through a warmed
    :class:`repro.serving.engine.TransformEngine` built for the same model
    set — shape-bucketed (zero recompiles at varying q) and optionally
    sharded over a serving mesh.  The engine path is bit-identical to the
    direct path at matched dtype.

    ``out_sharding`` (or a ``transform_out_sharding`` attribute left on the
    first model by :func:`fit`) places the result; the default returns host
    numpy.
    """
    if batch_size is not None and batch_size < 1:
        raise ValueError(f"batch_size must be a positive integer, got {batch_size}")
    if out_sharding is None and models:
        out_sharding = getattr(models[0], "transform_out_sharding", None)
    if engine is not None:
        if not engine.matches(models):
            raise ValueError(
                "engine was built for a different model set; build a "
                "TransformEngine over exactly these models"
            )
        out = engine.transform(Z)
        if dtype is not None:
            out = np.asarray(out).astype(np.dtype(dtype), copy=False)
        return jax.device_put(out, out_sharding) if out_sharding is not None else out
    with obs.span("transform/plan"):
        plan, fused_eval = _fused_plan_and_eval(models) if models else (None, None)
    if plan is None:
        out = _legacy_feature_transform(models, Z, dtype=dtype)
        return jax.device_put(out, out_sharding) if out_sharding is not None else out
    with obs.span("transform/eval"):
        Z = np.asarray(Z)
        q = Z.shape[0]
        out_dtype = np.dtype(dtype) if dtype is not None else plan.dtype
        if plan.num_features == 0:
            out = np.zeros((q, 0), out_dtype)
            return jax.device_put(out, out_sharding) if out_sharding is not None else out
        Zd = Z.astype(plan.dtype, copy=False)
        if batch_size is None or batch_size >= q:
            if q == 1:
                # XLA lowers single-row matmuls as gemv with a different
                # accumulation pattern than the q >= 2 gemm path; evaluate at
                # q=2 so direct, chunked and serving-bucket paths all see the
                # same row-stable lowering (bit-identical results).
                pad = np.zeros((2, Z.shape[1]), plan.dtype)
                pad[:1] = Zd
                out = fused_eval(jnp.asarray(pad))[:1]
            else:
                out = fused_eval(jnp.asarray(Zd))
            if out_sharding is not None:
                return jax.device_put(out, out_sharding)
            return np.asarray(out).astype(out_dtype, copy=False)
        out = np.empty((q, plan.num_features), out_dtype)
        # chunks must be >= 2 rows so no chunk hits the single-row gemv lowering
        # (see the q == 1 branch above); the output rows are unchanged
        batch_size = max(batch_size, 2)
        for start in range(0, q, batch_size):
            chunk = Zd[start : start + batch_size]
            if chunk.shape[0] < batch_size:  # pad trailing chunk: one trace only
                pad = np.zeros((batch_size, Z.shape[1]), plan.dtype)
                pad[: chunk.shape[0]] = chunk
                res = fused_eval(jnp.asarray(pad))[: chunk.shape[0]]
            else:
                res = fused_eval(jnp.asarray(chunk))
            out[start : start + batch_size] = np.asarray(res).astype(
                out_dtype, copy=False
            )
        return jax.device_put(out, out_sharding) if out_sharding is not None else out


__all__ = [
    "AUTO_SHARD_MIN_M",
    "MethodEntry",
    "OAVI_VARIANTS",
    "PlanConstants",
    "VanishingIdealModel",
    "aggregate_fit_stats",
    "available_methods",
    "eval_with_constants",
    "feature_transform",
    "fit",
    "fit_classes",
    "load",
    "load_state_dict",
    "oavi_config_for",
    "plan_constants",
    "register",
    "resolve",
    "save",
    "save_state_dict",
    "update",
]
