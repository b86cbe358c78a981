"""How the program gets a selection function from a group's embeddings:
its dense similarity kernel (``create_kernel``), wrapped by
``<function>.from_kernel``.

All groups of one call are built in one jitted program named
``bench_similarity``, so that the trace can tell it from the selection's.
"""
from __future__ import annotations

import jax

PROGRAM = "bench_similarity"


def program(config: dict):
    """A jitted call from stacked embeddings (groups, n, dim) to a tuple of
    each group's (n, n) kernel."""
    from repro.core import create_kernel

    metric = config["metric"]

    def bench_similarity(xs):
        return tuple(create_kernel(x, metric=metric) for x in xs)

    return jax.jit(bench_similarity)


def spec(config: dict, optimizer: str, kernel):
    """The request for one group's selection."""
    import repro.core as core

    fn = getattr(core, config["function"]).from_kernel(kernel, use_kernel=config["use_kernel"])
    return core.SelectionSpec(fn, config["budget"], optimizer)
