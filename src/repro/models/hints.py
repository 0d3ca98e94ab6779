"""Sharding hints: `with_sharding_constraint` helpers that are no-ops when
no mesh is active (CPU unit tests), and axis-aware when lowering under the
production mesh.

Why these exist: GSPMD propagates shardings through reshapes/transposes
heuristically, and the attention head split (B, S, H*dh) -> (B, S, H, dh)
with H not divisible by the model axis makes it fall back to *replicating*
the tensor ("involuntary full rematerialization") — which silently inflates
per-device FLOPs by the data-parallel degree.  Pinning the batch axes at
block boundaries and the head/feature axes where divisible keeps the
partitioner on the intended plan.  (Measured: qwen2-1.5b train went from
8x over the analytic roofline to ~1x after these hints — EXPERIMENTS.md.)
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
from jax.sharding import PartitionSpec as P


def current_mesh():
    """The mesh set by ``jax.set_mesh`` for this trace, or None."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def axis_sizes() -> Dict[str, int]:
    m = current_mesh()
    return {} if m is None else dict(m.shape)


def batch_axes() -> Tuple[str, ...]:
    sizes = axis_sizes()
    return tuple(a for a in ("pod", "data") if a in sizes)


def constrain(x, *spec):
    """with_sharding_constraint if a mesh is active; axes not present in the
    mesh are dropped to None.  ``spec`` entries: None | str | tuple of str |
    ("model?", dim_size) — the '?' form shards over model only if the given
    dimension size is divisible by the model-axis size."""
    sizes = axis_sizes()
    if not sizes:
        return x
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, tuple) and len(s) == 2 and s[0] == "model?":
            msz = sizes.get("model", 0)
            clean.append("model" if msz and s[1] % msz == 0 else None)
        elif isinstance(s, tuple):
            kept = tuple(a for a in s if a in sizes)
            clean.append(kept if kept else None)
        else:
            clean.append(s if s in sizes else None)
    spec = P(*clean)
    # Explicit mesh axes (jax.make_mesh's default) carry the sharding in the
    # array type and move it with reshard; Auto axes take a constraint
    if jax.sharding.AxisType.Explicit in current_mesh().axis_types:
        return jax.sharding.reshard(x, spec)
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_batch(x):
    """Pin the leading dim to the batch axes, rest unconstrained... except we
    explicitly mark them None to stop bad propagation."""
    ba = batch_axes()
    if not ba:
        return x
    rest = [None] * (x.ndim - 1)
    return constrain(x, ba if len(ba) > 1 else ba[0], *rest)
