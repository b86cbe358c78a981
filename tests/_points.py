"""Seeded point clouds and distance matrices shared by the test modules.

Kept out of ``conftest.py`` on purpose: ``tests/`` and ``bench/tests/`` each
have a ``conftest.py`` outside any package, so both import as the module
``conftest`` and only one can be imported from by name."""
import numpy as np


def make_points(rng, n, d=8):
    return rng.normal(size=(n, d)).astype(np.float32)


def make_dist(rng, n, d=8):
    x = make_points(rng, n, d)
    d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    return np.sqrt(np.maximum(d2, 0)).astype(np.float32)
