"""Seeded embeddings for the benchmark's deployments.

A copy of ``chip_smoke.py``'s generator, kept here so that an edit to the
smoke test cannot move the yardstick.  Each group (a class of a training
set, or the candidate list of one query) is a mixture of ``modes`` modes in
a ``latent``-wide space, projected to ``dim`` and multiplied by ``scale``:
vectors of low intrinsic dimension, as a network's last layers give.
``activation`` shapes them:

- ``relu``: non-negative, as post-ReLU features are;
- ``softmax``: probability vectors, as a classifier's outputs are.  The
  gradient of the cross-entropy loss with respect to the logits is the
  softmax minus the one-hot label, which is the same for every member of a
  class, so within a class these vectors lie at the gradients' distances;
- ``none``: the projection as it is.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, *stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits) and
    an optional stream of small integers (an epoch, a role)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0xFFFFFFFF)
    for word in (seed >> 32, *stream):
        k = jax.random.fold_in(k, word & 0xFFFFFFFF)
    return k


ACTIVATIONS = {"relu": jax.nn.relu, "softmax": jax.nn.softmax, "none": lambda x: x}


@partial(jax.jit, static_argnames=("groups", "n", "dim", "latent", "modes", "scale", "activation"))
def embeddings(
    k: jax.Array,
    groups: int,
    n: int,
    dim: int,
    latent: int = 32,
    modes: int = 16,
    scale: float = 1.0,
    activation: str = "relu",
) -> jax.Array:
    """(groups, n, dim) float32 embeddings, made on the device in one call."""
    ks = jax.random.split(k, 5)
    proj = jax.random.normal(ks[0], (latent, dim)) / np.sqrt(latent)
    centers = 3.0 * jax.random.normal(ks[1], (groups, 1, latent))
    mode_means = centers + jax.random.normal(ks[2], (groups, modes, latent))
    which = jax.random.randint(ks[3], (groups, n), 0, modes)
    z = jax.vmap(lambda m, w: m[w])(mode_means, which)
    z = z + 0.5 * jax.random.normal(ks[4], (groups, n, latent))
    x = jnp.einsum("gnl,ld->gnd", z, proj, precision="highest")
    return ACTIVATIONS[activation](scale * x)


def for_config(config: dict, seed: int, *stream: int, groups: int | None = None) -> jax.Array:
    """The embeddings a configuration describes, for ``seed`` and ``stream``."""
    e = config["embeddings"]
    return embeddings(
        key(seed, *stream),
        groups=config["groups"] if groups is None else groups,
        n=config["n"],
        dim=config["dim"],
        latent=e["latent"],
        modes=e["modes"],
        scale=e.get("scale", 1.0),
        activation=e["activation"],
    )
