"""How the program gets a selection function from a group's embeddings
without a kernel: ``<function>.from_features``, whose sweeps compute the
similarity from the features as they go (the matrix-free path).

All groups of one call are split out of the stacked embeddings in one
jitted program named ``bench_features``, so that the trace can tell it from
the selection's.
"""
from __future__ import annotations

import jax

PROGRAM = "bench_features"


def program(config: dict):
    """A jitted call from stacked embeddings (groups, n, dim) to a tuple of
    each group's (n, dim) features."""

    def bench_features(xs):
        return tuple(xs)

    return jax.jit(bench_features)


def spec(config: dict, optimizer: str, x):
    """The request for one group's selection."""
    import repro.core as core

    fn = getattr(core, config["function"]).from_features(
        x, metric=config["metric"], use_kernel=config["use_kernel"]
    )
    return core.SelectionSpec(fn, config["budget"], optimizer)
