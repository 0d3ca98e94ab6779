"""Launch-layer smoke tests: train/serve drivers on single-device CPU."""

import os
import subprocess
import sys
import tempfile

import pytest

from repro import configs
from repro.launch.serve import serve
from repro.launch.train import train
from repro.optim import AdamW


def test_train_driver_reduced_config(tmp_path):
    """Driver mechanics: steps run, losses finite, checkpoints commit.
    (Same-batch loss descent is covered by test_models.test_arch_smoke_train_step;
    20 distinct 2x32-token batches are too few to show cross-batch descent.)"""
    import numpy as np
    cfg = configs.get_reduced("phi4-mini-3.8b")
    opt = AdamW(peak_lr=1e-3, warmup_steps=5, total_steps=20)
    report = train(cfg, steps=20, global_batch=2, seq_len=32,
                   ckpt_dir=str(tmp_path / "ck"), ckpt_every=10, opt=opt)
    losses = report["losses"]
    assert len(losses) == 20
    assert np.isfinite(losses).all()
    # checkpoints were committed
    from repro.checkpoint import store
    assert store.latest_step(str(tmp_path / "ck")) == 20


def test_train_driver_resume(tmp_path):
    cfg = configs.get_reduced("qwen3-8b")
    opt = AdamW(peak_lr=1e-3, warmup_steps=2, total_steps=20)
    d = str(tmp_path / "ck")
    train(cfg, steps=10, global_batch=2, seq_len=16, ckpt_dir=d,
          ckpt_every=5, opt=opt)
    from repro.checkpoint import store
    assert store.latest_step(d) == 10
    # second run resumes from step 10 and continues
    report = train(cfg, steps=5, global_batch=2, seq_len=16, ckpt_dir=d,
                   ckpt_every=5, opt=opt)
    assert report["final_step"] == 15
    assert store.latest_step(d) == 15


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "xlstm-1.3b"])
def test_serve_driver_generates(arch):
    cfg = configs.get_reduced(arch)
    out = serve(cfg, batch=2, prompt_len=8, gen_tokens=6, seed=0)
    assert out["generated"].shape == (2, 6)
    assert (out["generated"] >= 0).all()
    assert (out["generated"] < cfg.vocab_size).all()


def test_serve_rejects_encoder_only():
    cfg = configs.get_reduced("hubert-xlarge")
    with pytest.raises(ValueError, match="encoder-only"):
        serve(cfg, batch=1, prompt_len=4, gen_tokens=2)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """The CLIs' compile cache goes to $JAX_COMPILATION_CACHE_DIR when set
    (the code sets nothing) and to <repo>/.jax_cache otherwise.  Checked in
    a child process: the tests themselves never turn the cache on."""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = (
        "import json, jax; from repro.launch import compile_cache; "
        "d = compile_cache.enable(); "
        "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path / "cache") if from_env else os.path.join(repo, ".jax_cache")
    assert returned == configured == want
