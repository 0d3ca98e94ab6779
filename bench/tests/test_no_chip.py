"""Without a TPU a run fails and prints no result line."""

import os
import subprocess
import sys

from bench import deploy


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(deploy.BENCH / "run.py"), "--workload", "appendix_c-fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(deploy.ROOT), env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout
