"""Shared utilities: pytree dataclasses, tie-breaking argmax, bit-stable
sums, concave fns, exact self-distance, and the compile cache location."""
from __future__ import annotations

import dataclasses
import math
import os
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def pytree_dataclass(cls=None, *, meta_fields: tuple[str, ...] = ()):
    """Register a (frozen) dataclass as a JAX pytree.

    ``meta_fields`` are static (hashed into the treedef); everything else is a
    leaf/data field.
    """

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        data_fields = tuple(
            f.name for f in dataclasses.fields(c) if f.name not in meta_fields
        )
        jax.tree_util.register_dataclass(
            c, data_fields=data_fields, meta_fields=tuple(meta_fields)
        )
        return c

    if cls is None:
        return wrap
    return wrap(cls)


def first_argmax(x: jax.Array) -> jax.Array:
    """Index of the first occurrence of the maximum (paper's tie rule)."""
    return jnp.argmax(x)


def masked_first_argmax(x: jax.Array, valid: jax.Array) -> jax.Array:
    """First argmax over entries where ``valid`` is True."""
    return jnp.argmax(jnp.where(valid, x, NEG_INF))


def tree_sum(x: jax.Array, axis: int) -> jax.Array:
    """Sum over ``axis`` as a balanced tree of elementwise adds.

    ``x.sum(axis)`` is one XLA reduce whose accumulation order the compiler
    picks per program: on a TPU, and on the CPU under JAX 0.9, a served wave
    (candidates padded to the n-bucket, vmapped over B) and sequential
    ``solve`` reduce the same column in different orders and drift by an
    ulp, which breaks served == sequential bit-identity.  Halving the axis
    with elementwise adds fixes the order for every shape and batch width
    (an odd length pads one exact ``+ 0.0``).

    The axis is moved to the front and flattened, so each level adds two
    contiguous halves.  Slicing a tiled minor axis instead makes XLA lay
    out everything that feeds ``x`` with that axis major, and a served FL
    wave would copy its whole kernel twice per greedy step (compiled for a
    v5e)."""
    x = jnp.moveaxis(x, axis, 0)
    rest = x.shape[1:]
    width = math.prod(rest)
    flat = x.reshape(-1)
    while flat.shape[0] > width:
        if (flat.shape[0] // width) % 2:
            flat = jnp.pad(flat, (0, width))
        half = flat.shape[0] // 2
        flat = flat[:half] + flat[half:]
    return flat.reshape(rest)


# The fixed summation order shared by the XLA gain sweeps of facility
# location and feature-based functions and their Pallas kernels: the summed
# axis in BLOCK-wide blocks, added block after block into one accumulator
# of that width, then a tree_sum of the accumulator.  The order does not
# depend on a kernel's tile size, so each side tiles as suits it.  BLOCK is
# one lane tile wide and sixteen sublane tiles tall, so a block is
# tile-aligned whichever of its two minor axes XLA lays the summed axis out
# on.  That matters: a greedy loop's column gathers make XLA keep a kernel
# with its rows on lanes, and a block narrower than a lane tile would have
# the loop copy the whole kernel back every step (compiled for a v5e).  The
# Pallas FL kernel reads the kernel in that layout, rows on lanes, and adds
# its BLOCK-lane blocks of a row tile one after another.
BLOCK = 128


def add_blocks(acc: jax.Array, x: jax.Array, axis: int) -> jax.Array:
    """``acc`` plus each ``acc``-wide block of ``x`` along ``axis``, block
    after block.  That axis of ``x`` is a multiple of ``acc``'s.  Every
    slice starts at a multiple of the width, so inside a Pallas TPU kernel
    it stays aligned to the vreg tiling."""
    size = acc.shape[axis]
    for b in range(x.shape[axis] // size):
        acc = acc + jax.lax.slice_in_dim(x, b * size, (b + 1) * size, axis=axis)
    return acc


def tiled_sum(
    terms: Callable[..., jax.Array],
    operands: tuple[jax.Array, ...],
    in_axes: tuple[int, ...],
    axis: int,
) -> jax.Array:
    """Sum ``terms(*operands)`` over its ``axis`` in the Pallas kernels'
    order: BLOCK-wide blocks added in sequence into a zero accumulator
    (:func:`add_blocks`), then :func:`tree_sum` of the accumulator.  The
    operands' last partial block is zero-padded, as the kernels pad or mask
    them; terms of zero operands must be +0.

    ``terms`` is elementwise along the summed axis; operand ``k`` is sliced
    along its ``in_axes[k]``.  The blocks are static slices that XLA fuses
    into one pass, so the matrix of terms is never written out.
    Elementwise adds in a fixed order, so an XLA sweep served at any batch
    width and n-bucket equals sequential ``solve`` and its Pallas kernel
    bit for bit."""
    length = operands[0].shape[in_axes[0]]

    def block(lo: int) -> jax.Array:
        def piece(a, ax):
            a = jax.lax.slice_in_dim(a, lo, min(lo + BLOCK, length), axis=ax)
            pad = [(0, 0)] * a.ndim
            pad[ax] = (0, BLOCK - a.shape[ax])
            return jnp.pad(a, pad)

        return terms(*(piece(a, ax) for a, ax in zip(operands, in_axes)))

    shape = jax.eval_shape(lambda: block(0))
    acc = jnp.zeros(shape.shape, shape.dtype)
    for lo in range(0, length, BLOCK):
        acc = acc + block(lo)
    return tree_sum(acc, axis)


def fl_gain_sums(sim: jax.Array, curmax: jax.Array) -> jax.Array:
    """Facility-location gains ``sum_i max(sim[i, j] - curmax[i], 0)`` of
    every column ``j``, in the Pallas FL kernel's order (:func:`tiled_sum`)."""
    return tiled_sum(
        lambda s, cm: jnp.maximum(s - cm[:, None], 0.0), (sim, curmax), (0, 0), 0
    )


CONCAVE_FNS: dict[str, Callable[[jax.Array], jax.Array]] = {
    # g(0) = 0 and concave increasing on x >= 0 — paper supports log / sqrt / inverse.
    "sqrt": lambda x: jnp.sqrt(jnp.maximum(x, 0.0)),
    "log": lambda x: jnp.log1p(jnp.maximum(x, 0.0)),
    "inverse": lambda x: x / (1.0 + jnp.maximum(x, 0.0)),
}


def get_concave(name: str) -> Callable[[jax.Array], jax.Array]:
    if name not in CONCAVE_FNS:
        raise ValueError(f"unknown concave fn {name!r}; choose from {sorted(CONCAVE_FNS)}")
    return CONCAVE_FNS[name]


def mask_from_indices(idxs: Any, n: int) -> jax.Array:
    """(k,) int indices (possibly with -1 padding) -> (n,) bool mask."""
    idxs = jnp.asarray(idxs, jnp.int32)
    valid = idxs >= 0
    return jnp.zeros((n,), bool).at[jnp.where(valid, idxs, 0)].set(valid, mode="drop")


# similarity metrics computed from the squared distance d2 = xx + yy - 2 x.y
DISTANCE_METRICS = ("euclidean", "rbf")


def exact_self_distance(d2: jax.Array, row0, col_ids: jax.Array) -> jax.Array:
    """Zero the (rows, cols) block ``d2`` where a column IS a row:
    ``col_ids[j] == row0 + i``; ids < 0 never match.

    ``xx + yy - 2 x.y`` cancels catastrophically for a point against
    itself: float32 leaves a residue of about ``1e-7 * |x|^2`` there, and
    the euclidean similarity's square root turns it into a self-similarity
    off by up to a few percent.  The distance of a point to itself is 0 by
    definition, so it is set, not computed."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    return jnp.where(rows == col_ids.reshape(1, -1), 0.0, d2)


# src/repro/common.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """Directory of JAX's persistent compile cache for this program:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.

    The path is fixed, never temporary or per-process: a compile is found
    again only under the directory it was written to."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache at :func:`compile_cache_dir`.

    Entry points call this before their first compile, never at import.
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it by itself and
    nothing is changed here.  Returns the directory in use."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
