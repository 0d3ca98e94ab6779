"""The paper's Appendix C data set.

Copied from ``repro.data.synthetic.appendix_c`` (same draws, same order) so
that later edits to the program cannot move the benchmark's inputs.  Class 1
lies near ``x1^2 + 0.01 x2 + x3^2 = 1``, class 2 near ``x1^2 + x3^2 = 1.3``,
both perturbed by N(0, noise^2).
"""

from __future__ import annotations

import numpy as np


def make(seed: int, m: int, noise: float, **_):
    rng = np.random.default_rng(seed)
    m1 = m // 2
    m2 = m - m1
    x2 = rng.uniform(0.0, 1.0, m1)
    theta = rng.uniform(0.0, 2.0 * np.pi, m1)
    r2 = np.maximum(1.0 - 0.01 * x2, 0.0)
    x1 = np.sqrt(r2) * np.cos(theta)
    x3 = np.sqrt(r2) * np.sin(theta)
    c1 = np.stack([x1, x2, x3], axis=1)
    theta = rng.uniform(0.0, 2.0 * np.pi, m2)
    x1 = np.sqrt(1.3) * np.cos(theta)
    x3 = np.sqrt(1.3) * np.sin(theta)
    x2 = rng.uniform(0.0, 1.0, m2)
    c2 = np.stack([x1, x2, x3], axis=1)
    X = np.concatenate([c1, c2], axis=0)
    X += rng.normal(0.0, noise, X.shape)
    y = np.concatenate([np.zeros(m1, np.int32), np.ones(m2, np.int32)])
    perm = rng.permutation(m)
    return X[perm].astype(np.float32), y[perm]
