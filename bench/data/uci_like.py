"""A UCI-shaped stand-in: classes planted near random algebraic sets.

Copied from ``repro.data.synthetic.uci_like`` / ``_planted_class`` (same
draws, same order) so that later edits to the program cannot move the
benchmark's inputs.  Class ``c`` lies near ``sum_j w_j x_j^(2 + c % 2) = c0``
on its first three coordinates; the other coordinates are uniform on [0, 1];
every coordinate gets N(0, noise^2).
"""

from __future__ import annotations

import numpy as np


def _planted_class(rng, m: int, n: int, degree: int, noise: float):
    k = min(3, n)
    w = rng.uniform(0.5, 1.5, k)
    c = rng.uniform(0.5, 1.5)
    X = rng.uniform(0.0, 1.0, (m, n))
    s = (w * X[:, :k] ** degree).sum(axis=1)
    scale = (c / np.maximum(s, 1e-9)) ** (1.0 / degree)
    X[:, :k] *= scale[:, None]
    X += rng.normal(0.0, noise, X.shape)
    return X


def make(seed: int, m: int, n: int, classes: int, noise: float, **_):
    rng = np.random.default_rng(seed)
    sizes = [m // classes] * classes
    sizes[-1] += m - sum(sizes)
    Xs, ys = [], []
    for c, mc in enumerate(sizes):
        Xs.append(_planted_class(rng, mc, n, degree=2 + (c % 2), noise=noise))
        ys.append(np.full(mc, c, np.int32))
    X = np.concatenate(Xs, axis=0)
    y = np.concatenate(ys)
    perm = rng.permutation(m)
    return X[perm].astype(np.float32), y[perm]
