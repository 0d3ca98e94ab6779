"""Training driver: mesh + config + data pipeline + AdamW + fault tolerance.

Runs for real on whatever devices exist (reduced configs on CPU; the same
code path drives the production mesh on TPU).  Composes every substrate:

    config -> init params (sharded) -> deterministic token pipeline ->
    jit'd train_step (donated state) -> TrainLoop (async checkpoints,
    resume, straggler detection)

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1
"""

from __future__ import annotations

import argparse
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..data import lm as lm_data
from ..models import model as M
from ..optim import AdamW
from ..runtime import TrainLoop, TrainLoopConfig
from . import mesh as mesh_mod


def make_sharded_train_state(cfg, opt, mesh, seed: int = 0):
    """Init params + optimizer state directly into their shardings."""
    aparams = M.abstract_params(cfg, seed)
    pspecs = M.param_specs(cfg, aparams, mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    init = jax.jit(partial(M.init_params, cfg=cfg), out_shardings=pshard)
    with jax.set_mesh(mesh):
        params = init(jax.random.PRNGKey(seed))
    sspecs = opt.state_specs(pspecs)
    sshard = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                          is_leaf=lambda x: isinstance(x, P))
    opt_init = jax.jit(opt.init, out_shardings=sshard)
    with jax.set_mesh(mesh):
        opt_state = opt_init(params)
    return params, opt_state, pshard, sshard


def make_step(cfg, opt, mesh, pshard, sshard):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(M.loss_fn)(params, batch, cfg)
        params, opt_state = opt.update(params, grads, opt_state)
        return loss, params, opt_state

    return jax.jit(
        train_step,
        in_shardings=(pshard, sshard, None),
        out_shardings=(NamedSharding(mesh, P()), pshard, sshard),
        donate_argnums=(0, 1),
    )


def train(
    cfg,
    *,
    steps: int = 100,
    global_batch: int = 8,
    seq_len: int = 128,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    mesh=None,
    seed: int = 0,
    log_every: int = 10,
    opt: Optional[AdamW] = None,
) -> Dict[str, Any]:
    mesh = mesh or mesh_mod.make_local_mesh()
    opt = opt or AdamW(peak_lr=3e-4, warmup_steps=min(50, steps // 10 + 1),
                       total_steps=steps)
    params, opt_state, pshard, sshard = make_sharded_train_state(cfg, opt, mesh, seed)
    step_fn = make_step(cfg, opt, mesh, pshard, sshard)
    pipe = lm_data.PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len + 1,
        global_batch=global_batch, seed=seed,
    )

    losses = []
    state = {"params": params, "opt": opt_state}

    def batch_fn(step: int):
        tokens = lm_data.batch_for_mesh(pipe, step, mesh, M.batch_axes(mesh))
        return {"tokens": tokens}

    def wrapped_step(state, batch):
        with jax.set_mesh(mesh):
            loss, params, opt_state = step_fn(state["params"], state["opt"], batch)
        losses.append(float(loss))
        return {"params": params, "opt": opt_state}, {"loss": float(loss)}

    if ckpt_dir is not None:
        loop = TrainLoop(
            TrainLoopConfig(ckpt_dir=ckpt_dir, ckpt_every=ckpt_every),
            wrapped_step, batch_fn, state,
        )
        loop.try_resume()
        report = loop.run(steps)
        state = loop.state
    else:
        t0 = time.perf_counter()
        for i in range(steps):
            state, m = wrapped_step(state, batch_fn(i))
            if i % log_every == 0:
                print(f"step {i:5d} loss {m['loss']:.4f}", flush=True)
        report = {"final_step": steps, "seconds": time.perf_counter() - t0}
    report["losses"] = losses
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get_config(args.arch)
    report = train(
        cfg, steps=args.steps, global_batch=args.batch, seq_len=args.seq,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, seed=args.seed,
    )
    losses = report["losses"]
    print(f"done: {report.get('final_step')} steps; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    from . import compile_cache

    compile_cache.enable()
    main()
