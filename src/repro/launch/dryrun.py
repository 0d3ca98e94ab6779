import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init), which is why the docstring sits below them and
# `from __future__` is omitted in this module.

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds abstract params/caches (ShapeDtypeStruct, no
allocation), jits the appropriate step function with explicit in/out
shardings on the production mesh, and runs ``.lower().compile()``.  Success
proves the sharding configuration is coherent end-to-end (no sharding
mismatches, no unsupported collectives); the compiled artifact yields

* ``memory_analysis()``  — bytes/device (proves the cell fits or documents
  that it does not),
* ``cost_analysis()``    — HLO FLOPs and bytes for the roofline terms,
* the HLO text           — parsed for per-collective byte counts.

Results are appended to ``results/dryrun_<mesh>.json`` for
``benchmarks/roofline.py`` and EXPERIMENTS.md.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..models import model as M
from ..optim import AdamW
from . import mesh as mesh_mod

from .hlo_analysis import collective_bytes  # noqa: E402  (env must be set above)

# ---------------------------------------------------------------------------
# Per-cell lowering
# ---------------------------------------------------------------------------


def _sharded(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(
    arch_id: str,
    shape_name: str,
    mesh,
    *,
    optimizer: Optional[AdamW] = None,
    cfg=None,
):
    """Lower one (arch, shape) cell on ``mesh``.  Returns (lowered, meta)."""
    cfg = cfg or configs.get_config(arch_id)
    shape = configs.SHAPES[shape_name]
    ok, why = configs.cell_supported(cfg, shape)
    if not ok:
        raise ValueError(f"{arch_id} x {shape_name} skipped: {why}")

    aparams = M.abstract_params(cfg)
    pspecs = M.param_specs(cfg, aparams, mesh)
    pshard = _sharded(pspecs, mesh)
    bspecs = M.batch_specs(cfg, mesh, shape.kind, shape.global_batch)
    bshard = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}
    abatch = configs.input_specs(cfg, shape)

    if shape.kind == "train":
        opt = optimizer or AdamW()
        astate = jax.eval_shape(opt.init, aparams)
        sspecs = opt.state_specs(pspecs)
        sshard = _sharded(sspecs, mesh)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(M.loss_fn)(params, batch, cfg)
            params, opt_state = opt.update(params, grads, opt_state)
            return loss, params, opt_state

        fn = jax.jit(
            train_step,
            in_shardings=(pshard, sshard, bshard),
            out_shardings=(NamedSharding(mesh, P()), pshard, sshard),
        )
        with jax.set_mesh(mesh):
            lowered = fn.lower(aparams, astate, abatch)
    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            return M.prefill(params, batch, cfg, S_max=shape.seq_len)

        acache = M.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cshard = _sharded(M.cache_specs(cfg, acache, mesh), mesh)
        fn = jax.jit(
            prefill_step,
            in_shardings=(pshard, bshard),
            out_shardings=(NamedSharding(mesh, P()), cshard),
        )
        with jax.set_mesh(mesh):
            lowered = fn.lower(aparams, abatch)
    else:  # decode
        acache = M.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cshard = _sharded(M.cache_specs(cfg, acache, mesh), mesh)

        def serve_step(params, cache, token, pos):
            return M.decode_step(params, cache, token, pos, cfg)

        fn = jax.jit(
            serve_step,
            in_shardings=(pshard, cshard, bshard["token"], bshard["pos"]),
            out_shardings=(NamedSharding(mesh, P()), cshard),
            donate_argnums=(1,),
        )
        with jax.set_mesh(mesh):
            lowered = fn.lower(
                aparams, acache, abatch["token"], abatch["pos"]
            )

    meta = {"arch": arch_id, "shape": shape_name, "kind": shape.kind}
    return lowered, meta


def _cost_compile(arch_id: str, shape_name: str, mesh, n_periods: int) -> Dict:
    """Compile an ``n_periods``-deep, fully-unrolled variant for cost terms.

    XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    count, so the production scan-over-periods module under-reports FLOPs by
    ~n_periods x.  The unrolled 1- and 2-period compiles let us recover the
    exact per-period cost by differencing (collectives and bytes likewise).
    """
    import dataclasses as _dc

    cfg = configs.get_config(arch_id)
    cfg = _dc.replace(cfg, n_periods=n_periods, unroll_scan=True)
    lowered, _ = lower_cell(arch_id, shape_name, mesh, cfg=cfg)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
    }


def run_cell(arch_id: str, shape_name: str, mesh, mesh_name: str,
             *, with_cost: bool = True) -> Dict:
    t0 = time.perf_counter()
    lowered, meta = lower_cell(arch_id, shape_name, mesh)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    rec = {
        **meta,
        "mesh": mesh_name,
        "devices": int(len(mesh.devices.reshape(-1))),
        "time_lower_s": round(t_lower, 2),
        "time_compile_s": round(t_compile, 2),
        "flops_raw": float(cost.get("flops", -1.0)),
        "bytes_raw": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes_raw": coll,
        "memory": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
    }
    if with_cost:
        n = configs.get_config(arch_id).n_periods
        c1 = _cost_compile(arch_id, shape_name, mesh, 1)
        c2 = _cost_compile(arch_id, shape_name, mesh, 2)
        # per-period marginals clamped at 0: XLA occasionally CSEs the
        # 2-period module harder than the 1-period one, which would
        # otherwise extrapolate to nonsense (negative collectives)
        df = max(c2["flops"] - c1["flops"], 0.0)
        db = max(c2["bytes_accessed"] - c1["bytes_accessed"], 0.0)
        rec["flops"] = c1["flops"] + (n - 1) * df
        rec["bytes_accessed"] = c1["bytes_accessed"] + (n - 1) * db
        coll_true = {}
        for kind in list(c1["collectives"]):
            dk = max(c2["collectives"][kind] - c1["collectives"][kind], 0)
            coll_true[kind] = int(c1["collectives"][kind] + (n - 1) * dk)
        rec["collective_bytes"] = coll_true
        rec["cost_detail"] = {"p1": c1, "p2": c2, "n_periods": n}
    else:
        rec["flops"] = rec["flops_raw"]
        rec["bytes_accessed"] = rec["bytes_raw"]
        rec["collective_bytes"] = coll
    return rec


def run_oavi_cell(mesh, mesh_name: str, *, m_global: int = 4_194_304,
                  n_features: int = 57, Lcap: int = 256, Kcap: int = 64,
                  dtype: str = "float32") -> Dict:
    """The paper's technique on the production mesh: one OAVI degree step
    (fused border-eval + Gram + sequential acceptance) with the sample axis
    sharded over every data axis.  m is chosen spam-shaped (n=57) at ~4M
    samples; the collectives are the two Gram psums (L x K + K x K floats),
    m-independent — the weak-scaling signature of Theorem 4.3.
    """
    import jax.numpy as jnp

    from ..core.distributed import make_sharded_degree_step
    from ..core.oavi import OAVIConfig
    from ..core import ihb as ihb_mod

    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    cfg = OAVIConfig(psi=0.005, engine="fast", cap_terms=Lcap, dtype=dtype)
    step = make_sharded_degree_step(cfg, mesh, data_axes=axes)
    dt = jnp.dtype(dtype)
    aA = jax.ShapeDtypeStruct((m_global, Lcap), dt)
    aX = jax.ShapeDtypeStruct((m_global, n_features), dt)
    # the state is slimmed to the configured engine's factor set (here:
    # engine='fast' -> the Theorem 4.9 inverse only), matching what fit passes
    astate = jax.eval_shape(
        lambda: ihb_mod.init_state(
            Lcap, jnp.asarray(1.0, dt), dt, factors=cfg.ihb_factors()
        )
    )
    i32 = jnp.int32
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered = step.lower(
            aA, aX, astate,
            jax.ShapeDtypeStruct((), i32),
            jax.ShapeDtypeStruct((Kcap,), i32),
            jax.ShapeDtypeStruct((Kcap,), i32),
            jax.ShapeDtypeStruct((Kcap,), jnp.bool_),
            jax.ShapeDtypeStruct((), dt),
        )
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "arch": "oavi-gram-step",
        "shape": f"m{m_global // 1_000_000}M_n{n_features}_L{Lcap}_K{Kcap}",
        "kind": "oavi",
        "mesh": mesh_name,
        "devices": int(len(mesh.devices.reshape(-1))),
        "time_lower_s": round(t_lower, 2),
        "time_compile_s": round(t_compile, 2),
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes": coll,
        "memory": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--oavi", action="store_true",
                    help="lower the paper's OAVI degree step on the mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", type=str, default="results")
    args = ap.parse_args()

    mesh = mesh_mod.make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    os.makedirs(args.out, exist_ok=True)
    out_path = os.path.join(args.out, f"dryrun_{mesh_name}.json")
    results = []
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"]) for r in results}

    if args.oavi:
        rec = run_oavi_cell(mesh, mesh_name)
        results = [r for r in results
                   if (r["arch"], r["shape"]) != (rec["arch"], rec["shape"])]
        results.append(rec)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
        print(f"  oavi ok: flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
              f"coll={rec['collective_bytes']['total']:.3e}")
        if not args.all:
            return

    if args.all:
        cells = [
            (a, s) for a, s, ok, _ in configs.all_cells() if ok
        ]
    else:
        cells = [(args.arch, args.shape)]

    for arch_id, shape_name in cells:
        if (arch_id, shape_name) in done:
            print(f"[skip-done] {arch_id} x {shape_name}")
            continue
        print(f"[dryrun:{mesh_name}] {arch_id} x {shape_name} ...", flush=True)
        # cost-extraction compiles (1/2-period unrolled) feed the roofline
        # table, which is single-pod only; the multi-pod pass proves the
        # "pod" axis shards and records raw per-device numbers.
        rec = run_cell(arch_id, shape_name, mesh, mesh_name,
                       with_cost=not args.multi_pod)
        results.append(rec)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
        print(
            f"  ok: flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
            f"coll={rec['collective_bytes']['total']:.3e} "
            f"compile={rec['time_compile_s']}s"
        )
    print(f"wrote {out_path} ({len(results)} cells)")


if __name__ == "__main__":
    from . import compile_cache

    compile_cache.enable()
    main()
