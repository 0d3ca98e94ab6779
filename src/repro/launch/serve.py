"""Serving driver: batched prefill + decode with per-block caches.

The same prefill/decode step functions the dry-run lowers for the
production mesh, driven for real on local devices (reduced configs on CPU).
Implements a minimal continuous-batching-style server core: a request batch
is prefETCHED together, then decoded lock-step; finished sequences are
masked (their slots keep decoding into a scratch position — the static-shape
SPMD analogue of slot recycling).

Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --batch 4 --prompt-len 32 --gen 32
"""

from __future__ import annotations

import argparse
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..models import model as M
from . import mesh as mesh_mod


def serve(
    cfg,
    *,
    batch: int = 4,
    prompt_len: int = 32,
    gen_tokens: int = 32,
    seed: int = 0,
    mesh=None,
    greedy: bool = True,
) -> Dict:
    if not cfg.supports_decode:
        raise ValueError(f"{cfg.name} is encoder-only; no decode path")
    mesh = mesh or mesh_mod.make_local_mesh()
    S_max = prompt_len + gen_tokens
    rng = np.random.default_rng(seed)
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)

    prefill = jax.jit(lambda p, b: M.prefill(p, b, cfg, S_max=S_max))
    decode = jax.jit(lambda p, c, t, pos: M.decode_step(p, c, t, pos, cfg))

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        logits, cache = prefill(params, {"tokens": prompts})
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(gen_tokens - 1):
        pos = jnp.full((batch,), prompt_len + i, jnp.int32)
        with jax.set_mesh(mesh):
            logits, cache = decode(params, cache, tok, pos)
        if greedy:
            tok = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        else:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
            tok = jax.random.categorical(key, logits[:, 0, :]).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    generated = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    return {
        "generated": generated,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tokens_per_s": batch * (gen_tokens - 1) / max(t_decode, 1e-9),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get_config(args.arch)
    out = serve(cfg, batch=args.batch, prompt_len=args.prompt_len, gen_tokens=args.gen,
                seed=args.seed)
    print(f"prefill {out['prefill_s']:.2f}s; decode {out['decode_s']:.2f}s; "
          f"{out['tokens_per_s']:.1f} tok/s")
    print("sample:", out["generated"][0][:16])


if __name__ == "__main__":
    from . import compile_cache

    compile_cache.enable()
    main()
